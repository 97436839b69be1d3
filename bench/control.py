#!/usr/bin/env python3
"""Readings of the compared numbers for the program and for the control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed this makes one run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the check) and prints, per
compared number, the program's reading and the control's. The control
is the plain reference put in the program's place and computed in
bfloat16, the precision below the float32 that the configuration
states: its answers to the same sampled requests go through the same
comparison. A limit lies above every sound reading and below the
control's. The benchmark's own runs do not run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_numbers(rec: dict, art, edges, exact, seed: int) -> dict:
    """The comparison's numbers for the bfloat16 reference's answers to
    the requests the check sampled from ``rec``."""
    import numpy as np

    from bench import check, reference
    mix = rec["mix"]
    rng = np.random.default_rng([int(seed), 3])
    reqs = check.sample(rec["requests"], int(mix["check_samples"]), rng)
    ctrl = []
    for r in reqs:
        if mix["kind"] == "topk":
            row = reference.horner_row(art, edges, r["u"], rnd=reference.bf16)
            sv, si = reference.topk_of(row, min(int(mix["k"]), art.n))
            answer = (sv.astype(np.float32), si.astype(np.int32))
        else:
            answer = reference.pair(art, r["u"], r["v"], rnd=reference.bf16)
        ctrl.append(dict(r, answer=answer))
    if mix["kind"] == "topk":
        return check.topk_numbers(ctrl, int(mix["k"]), art, edges, exact)
    return check.pair_numbers(ctrl, art, exact)


def readings(cell: dict, seed: int, seconds: float, *, t_start: float,
             require_tpu: bool = True) -> dict:
    from bench import harness
    out = {}

    def keep(rec, art, edges, exact):
        out["control"] = control_numbers(rec, art, edges, exact, seed)

    rec = harness.run(cell, seed, seconds, False, t_start=t_start,
                      require_tpu=require_tpu, keep=keep)
    out["program"] = {k: c["value"] for k, c in rec["checks"].items()}
    out["attempted"] = len(rec["requests"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import harness
    cell = harness.load_cell(args.workload)
    t_start = T_START
    for seed in args.seeds:
        try:
            got = readings(cell, seed, args.seconds, t_start=t_start)
        except harness.NoChip as e:
            print(str(e), file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
