#!/usr/bin/env python3
"""Run one benchmark cell traced, with the program's span recorder on,
and print what its spans and scopes say.

    python3 bench/program_run.py --workload <cell> --seed <n> --seconds <s> [--keep <dir>]

The run is ``bench/run.py --trace 1``'s (``harness.run``) with
``repro.serve.spans`` enabled from before the warm-up to the end.
It prints one line ``program {...}``, then that run's facts and
result lines as bench/run.py does. The program line holds the
per-layer numbers that the spans and scopes give, the requests whose
latency parts fail to tile it, what the batch and engine spans'
attributes say (fill, batches ahead at close, close reasons, LRU
misses, padding), the hand-off per batch from ``batch_log``, the
device's idle seconds per innermost span, the ten longest idle gaps
named by span, and the median and summed seconds of each span in the
window. With
``--keep`` the profiler trace and the served programs' optimized HLO
texts are copied into <dir>. Exits 2 without a TPU, as bench/run.py.

``harness.run`` keeps the window's tickets, the frontend, the engines
and the trace file to itself; :func:`capture` wraps ``traffic.run_open`` /
``run_closed``, ``harness.Spans`` and ``trace.reduce_xplane`` for the
run to see them, and changes nothing they do.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def capture(keep_dir: str):
    """While open, keep what ``harness.run`` hides in the dict it
    yields: the window's ``Sent`` requests, the frontend, the engines,
    and a copy of the trace file in ``keep_dir``."""
    from bench import harness, trace, traffic
    got = {"sent": [], "frontend": None, "engines": [], "xplane": None}
    saved = (traffic.run_open, traffic.run_closed, harness.Spans,
             trace.reduce_xplane)

    def keeping(fn):
        def drive(fe, *a, **kw):
            got["frontend"] = fe
            sent = fn(fe, *a, **kw)
            got["sent"].append(sent)
            return sent
        return drive

    class Spans(saved[2]):
        def __init__(self, engines, trace_on):
            super().__init__(engines, trace_on)
            got["engines"] += engines

    def reduce_and_keep(path):
        os.makedirs(keep_dir, exist_ok=True)
        got["xplane"] = os.path.join(keep_dir, os.path.basename(path))
        shutil.copyfile(path, got["xplane"])
        return saved[3](path)

    traffic.run_open = keeping(saved[0])
    traffic.run_closed = keeping(saved[1])
    harness.Spans = Spans
    trace.reduce_xplane = reduce_and_keep
    try:
        yield got
    finally:
        (traffic.run_open, traffic.run_closed, harness.Spans,
         trace.reduce_xplane) = saved


def run(cell: dict, seed: int, seconds: float, keep_dir: str, *,
        t_start: float, require_tpu: bool = True):
    """One traced run of ``cell`` with the recorder on; returns (run
    record, program summary, the programs' (name, HLO text) pairs)."""
    from bench import harness, program, scopes
    from repro.serve import spans
    with capture(keep_dir) as got:
        recorder = spans.Recorder(time.monotonic)
        spans.enable(recorder)
        try:
            rec = harness.run(cell, seed, seconds, True, t_start=t_start,
                              require_tpu=require_tpu)
        finally:
            spans.disable()
    texts = [t for eng in got["engines"] for t in eng.program_texts()]
    times = scopes.device_time(got["xplane"], scopes.module_maps(texts))
    out = summarize(rec, recorder.records, got["sent"][0], times,
                    program.idle(got["xplane"]))
    lo, hi = rec["window"]["t0"], rec["window"]["t_end"]
    out["batch_log_handoff_ms"] = program.batch_log_handoff_ms(
        got["frontend"].batch_log, lo, hi)
    out["dropped"] = recorder.dropped
    out["end_to_end"] = {m["name"]: harness.metric_reader(m["name"])(rec)
                         for m in cell["end_to_end"]}
    return rec, out, texts


def summarize(rec, records, sent, times, idle) -> dict:
    from bench import program, scopes
    lo, hi = rec["window"]["t0"], rec["window"]["t_end"]
    reqs = [{"sched": r.sched, "sent": r.sent, "admit": r.ticket.submit_t,
             "id": r.ticket.id,
             "done": (r.ticket.fulfil_t if r.ticket.done()
                      and not r.ticket.shed else None)} for r in sent]
    parts = program.request_parts(reqs, records)
    kind = rec["mix"]["kind"]
    engine = "pairs" if kind == "pair" else "topk"
    per_layer = {
        f"frontend.queue_wait_ms.{kind}": program.queue_wait_ms(parts),
        f"frontend.handoff_ms.{kind}": program.handoff_ms(parts),
        f"engine.host_ms.{kind}": program.engine_host_ms(
            records, engine, lo, hi),
        f"frontend.worker_gap_ms.{kind}": program.worker_gap_ms(
            records, engine, lo, hi),
        "device.fold_ms.pair": scopes.scope_ms(times, "pair",
                                               "sling.pair.fold"),
        "device.join_ms.pair": scopes.scope_ms(times, "pair",
                                               "sling.pair.join"),
        "device.push_ms.topk": scopes.scope_ms(times, "topk", "sling.push"),
        "device.select_ms.topk": scopes.scope_ms(times, "topk",
                                                 "sling.select"),
    }
    names = sorted({r[0] for r in records})
    span_s = {}
    for name in names:
        d = [r[4] - r[3] for r in program.named(records, name, lo, hi)]
        if d:
            span_s[name] = {"count": len(d), "median_ms": 1e3 * sorted(d)[
                len(d) // 2], "sum_s": sum(d)}
    done = [p for p in parts if p is not None]
    part_names = ("sent-sched", "admit-sent", "close-admit",
                  "start-close", "done-start")
    return {
        "per_layer": per_layer,
        "tiling_violations": program.tiling_violations(reqs, parts),
        "answered": len(done),
        "request_parts_median_ms": (
            {n: 1e3 * sorted(p[i] for p in done)[len(done) // 2]
             for i, n in enumerate(part_names)} if done else None),
        "batches": program.batch_summary(records, lo, hi),
        "engine": program.engine_summary(records, engine, lo, hi),
        "compiles_in_launch": sum(r[6].get("compiles", 0) for r in
                                  program.named(records,
                                                "sling.engine.launch",
                                                lo, hi)),
        "records": len(records),
        "scope_coverage": {k: m["mapped"] for k, m in times.items()},
        "spans": span_s,
        "idle_by_span": idle["idle_by_span"],
        "idle_gaps": idle["idle_gaps"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="copy the trace and HLO texts into this directory")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import harness
    cell = harness.load_cell(args.workload)
    keep = args.keep or str(harness.WORK / f"keep-{os.getpid()}")
    try:
        rec, out, texts = run(cell, args.seed, args.seconds, keep,
                              t_start=T_START)
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    print("program " + json.dumps(out), flush=True)
    harness.print_result(harness.result_line(cell, rec, True), rec)
    if args.keep:
        for name, text in texts:
            with open(os.path.join(keep, f"{name}.hlo.txt"), "w") as f:
                f.write(text)
    else:
        shutil.rmtree(keep, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
