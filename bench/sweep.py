#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

One set-up, then one window per rate, in the order given, through the
same warm frontend. Per rate it prints the requests sent, the latency
percentiles from the scheduled send, how late the generator ran, and
the backlog: requests not answered when the window closed. A rate is
sustained where the backlog stays near the requests of one batch and
p95 is flat. The cell's mix then offers about four fifths of the
highest sustained rate. The benchmark's own runs do not run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import numpy as np

    from bench import harness, traffic
    from repro.serve import FrontendConfig, ServeFrontend
    cell = harness.load_cell(args.workload)
    if cell["mix"]["loop"] != "open":
        print("only an open-loop cell has a rate to sweep", file=sys.stderr)
        return 2
    try:
        harness.device_facts(cell["chips"])
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    harness.WORK.mkdir(exist_ok=True)
    path = harness.WORK / f"sweep-{os.getpid()}.sling"
    rec = {"setup": {}}
    try:
        _, _, g, idx, _ = harness.set_up(cell["config"], args.seed,
                                             path, rec)
        with ServeFrontend(idx, g, FrontendConfig()) as fe:
            harness.warm_up(fe, cell["mix"], idx.n)
            print(json.dumps({"setup": rec["setup"], "index": rec["index"],
                              "setup_s": time.monotonic() - T_START}),
                  flush=True)
            for i, rate in enumerate(args.rates):
                mix = dict(cell["mix"], rate_per_s=rate)
                rng = np.random.default_rng([args.seed, 10 + i])
                t0 = time.monotonic()
                sent = traffic.run_open(fe, mix, idx.n, args.seconds, rng, t0)
                end = t0 + args.seconds
                backlog = sum(not r.ticket.done() for r in sent)
                for r in sent:
                    r.ticket.result(timeout=120)
                lat = np.array([(r.ticket.fulfil_t - r.sched) * 1e3
                                for r in sent])
                late = np.array([(r.sent - r.sched) * 1e3 for r in sent])
                sizes = [b.size for b in list(fe.batch_log)
                         if t0 <= b.closed <= end]
                print(json.dumps({
                    "rate": rate, "sent": len(sent),
                    "backlog_at_close": backlog,
                    "p50_ms": float(np.percentile(lat, 50)),
                    "p95_ms": float(np.percentile(lat, 95)),
                    "p99_ms": float(np.percentile(lat, 99)),
                    "late_p99_ms": float(np.percentile(late, 99)),
                    "batches": len(sizes),
                    "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
                    "window_s": time.monotonic() - t0,
                    "closed_late_s": time.monotonic() - end}), flush=True)
    finally:
        path.unlink(missing_ok=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
