#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program under ``src/``.
The cell is a ``workloads`` entry of ``BENCHMARK.json``. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. Exits 2, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is missing.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under {ROOT}/src", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        rec = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    line = harness.result_line(cell, rec, bool(args.trace))
    harness.print_result(line, rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
