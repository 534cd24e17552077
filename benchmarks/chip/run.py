#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of `BENCHMARK.json`.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the TPU chips
the cell asks for. Set-up draws the weights from the seed, builds and
compiles the program's served steps and warms up every shape the cell uses;
then the cell's traffic generator runs for `--seconds`. With `--trace 0` the
result carries the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics, read from a profiler trace of the window. Every run checks the
tokens the window served against the float32 reference (`check.py`).

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` `breakdown`,
and last `check`, each compared number beside its limit. A run that finds no
TPU, fewer chips than the cell asks for, or a chip missing from `peaks.py`
exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program to measure: {ROOT / 'src' / 'repro'} is missing",
              file=sys.stderr)
        return 2
    # the script's own directory leaves the path: its modules are `chip.*`
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT / "benchmarks")]
    from chip import harness

    cell = harness.load_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload, HERE)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"not measured: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
