"""python -m benchmark.run --workload <name>
    --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once, on the machine it is started on.
Exits non-zero, with no result line, when jax finds no TPU or fewer
chips than the cell asks for.

`--fault <name>` is the cell's CONTROL: the same run with one guarantee
broken under the timed path (benchmark/faults.py); it has to print
`correct: false`.  Run by hand on the chip and, at toy size, by
tests/benchmark; never by the benchmark's own runs."""

from __future__ import annotations

import argparse
import asyncio
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from benchmark import harness, manifest
    try:
        man = manifest.Manifest()
        man.workload(args.workload)
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        harness.look_for_chip(man.workload(args.workload)["chips"])
    except harness.NoAccelerator as e:
        print(f"benchmark: no TPU, refusing to measure: {e}",
              file=sys.stderr)
        return 3
    result = asyncio.run(harness.run_cell(
        man, args.workload, args.seed, args.seconds, bool(args.trace),
        fault=args.fault))
    # `correct` false is a result, reported on the line; the exit code
    # says the run reached its end
    return 0 if result is not None else 1


if __name__ == "__main__":
    sys.exit(main())
