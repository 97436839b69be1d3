"""One run of one cell: set-up, measured window, answer check, and
the run record that the metric readers in ``bench/metrics/`` reduce.

Everything a cell needs is found by name: its entry in
``BENCHMARK.json``, its configuration file, ``bench/traffic/<mix>.json``,
``bench/limits/<cell>.json`` and ``bench/metrics/<metric>.py``.

A mix with ``writes`` (``bench/traffic.py``) builds the index on the
graph without its held-back edges, and a :class:`Writer` applies them
during the window through the program's update contract
(``update_index``, then ``ServeFrontend.swap_index``); the check holds
each answer to the index and graph of the epoch that served it.
"""
from __future__ import annotations

import copy
import gc
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
WORK = ROOT / ".bench_work"
DRAIN_S = 60.0            # an open-loop answer later than this is lost
WRITE_TIMES = ("due", "update_start", "update_end", "swap_start", "swap_end")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration, mix,
    limits and the metric entries it reports."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def reports(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": load_json(ROOT / conf["file"]),
        "mix": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
    }


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def device_facts(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``.jax_cache/`` of the checkout), keeping
    every program, however quick its compile."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class GcPauses:
    """The interpreter's collections while ``on``: (generation, start,
    seconds). A collection holds every thread of the process, the
    frontend's and the generator's alike."""

    def __init__(self):
        self.on = False
        self.pauses: list = []
        self._t0 = None

        def callback(phase, info):
            if not self.on:
                return
            if phase == "start":
                self._t0 = time.monotonic()
            elif self._t0 is not None:
                self.pauses.append((info["generation"], self._t0,
                                    time.monotonic() - self._t0))
                self._t0 = None
        gc.callbacks.append(callback)
        self._callback = callback

    def close(self):
        gc.callbacks.remove(self._callback)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache
    while ``on``: every request for a new executable."""
    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax
        self.on = False
        self.count = 0

        def listener(event, **kw):
            if self.on and event == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_listener(listener)


class Spans:
    """Host spans around each call into the engine (``engine.<kind>``),
    recorded while ``on``; in a traced run each is also a profiler
    annotation ``bench.engine.<kind>``."""

    def __init__(self, engines, trace: bool):
        import jax
        self.on = False
        self.spans: dict[str, list] = {}
        for eng in engines:
            for kind in ("pairs", "topk"):
                fn = getattr(eng, kind)

                def timed(*a, _fn=fn, _kind=kind, **kw):
                    if not self.on:
                        return _fn(*a, **kw)
                    t0 = time.monotonic()
                    if trace:
                        with jax.profiler.TraceAnnotation(
                                f"bench.engine.{_kind}"):
                            out = _fn(*a, **kw)
                    else:
                        out = _fn(*a, **kw)
                    self.spans.setdefault(f"engine.{_kind}", []).append(
                        (t0, time.monotonic(), len(a[0])))
                    return out
                setattr(eng, kind, timed)


def warm_up(fe, mix: dict, n: int) -> None:
    """One real request of the cell's kind through the frontend: the
    engine pads every batch to its fixed shape, so this compiles (or
    loads) every program the window runs."""
    from bench import traffic
    traffic.submit(fe, mix, 0, n - 1)
    fe.flush()
    t = traffic.submit(fe, mix, n - 1, 0)
    t.result(timeout=600)


class Writer:
    """Applies a mix's write batches during the window, on a thread of
    its own: at each batch's due time ``update_index`` on the served
    index, then the frontend's barrier swap of the repaired index and
    graph. ``records`` holds one dict per batch (times, the update's
    report, the swap's metrics, or the error that stopped the writer);
    ``epochs[e - 1]`` is a copy of the index as epoch e served it."""

    def __init__(self, fe, idx, g, src, dst, batches, seed: int):
        self.fe, self.idx, self.g = fe, idx, g
        self.src, self.dst, self.batches = src, dst, batches
        self.seed = seed
        self.records = [{"batch": i, "edges": hi - lo, "applied": False}
                        for i, (_, lo, hi) in enumerate(batches)]
        self.epochs: list = []
        self._stop = threading.Event()
        self._thread = None

    def start(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        name="bench-writer", daemon=True)
        self._thread.start()

    def join(self, deadline: float) -> list[dict]:
        """Wait for the writer until ``deadline``, then stop it at its
        next step; returns the batches' records as they stand. A batch
        not applied by then counts as failed."""
        self._thread.join(timeout=max(0.0, deadline - time.monotonic()))
        self.stop()
        out = [dict(w) for w in self.records]
        if self._thread.is_alive():
            for w in out:
                if not w["applied"]:
                    w.setdefault("error", "not done by the drain deadline")
        return out

    def stop(self) -> None:
        self._stop.set()

    def _run(self, t0: float) -> None:
        import jax

        from repro.core import build
        from repro.graph import csr
        none = np.zeros(0, np.int64)
        for i, (due, lo, hi) in enumerate(self.batches):
            w = self.records[i]
            w["due"] = t0 + due
            if self._stop.wait(max(0.0, t0 + due - time.monotonic())):
                return
            delta = csr.GraphDelta(add_src=self.src[lo:hi],
                                   add_dst=self.dst[lo:hi],
                                   del_src=none, del_dst=none)
            try:
                w["update_start"] = time.monotonic()
                with jax.profiler.TraceAnnotation("bench.write.update"):
                    rep = build.update_index(
                        self.idx, self.g, delta,
                        seed=(self.seed + 1 + i) % (2**31 - 1))
                w["update_end"] = time.monotonic()
                w["report"] = {
                    "touched": len(rep.touched),
                    "rows_repaired": rep.rows_repaired,
                    "targets_seeded": rep.targets_seeded,
                    "d_updated": rep.d_updated,
                    "width_grew": bool(rep.width_grew),
                    "stale": float(rep.stale),
                    "eps_stale": float(rep.eps_stale),
                    "needs_rebuild": bool(rep.needs_rebuild),
                    "secs": rep.secs}
                if self._stop.is_set():
                    return
                w["swap_start"] = time.monotonic()
                with jax.profiler.TraceAnnotation("bench.write.swap"):
                    w["swap"] = self.fe.swap_index(self.idx, rep.graph,
                                                   rep.affected)
                w["swap_end"] = time.monotonic()
            except Exception as e:
                w["error"] = f"{type(e).__name__}: {e}"
                return
            self.g = rep.graph
            self.epochs.append(copy.deepcopy(self.idx))
            w["applied"] = True


def set_up(conf: dict, seed: int, path, rec: dict, hold_back: int = 0):
    """Graph from the seed (without its last ``hold_back`` edges, which
    the writer applies), index build into ``path``, mmap load; the
    seconds of each go into ``rec["setup"]``."""
    from bench import graphs
    from repro.core import build
    from repro.core.index import SlingIndex
    from repro.graph import csr
    t = time.monotonic()
    src, dst = graphs.make_edges(conf["graph"])
    base = len(src) - hold_back
    g = csr.from_edges(conf["graph"]["n"], src[:base], dst[:base])
    rec["setup"]["graph_s"] = time.monotonic() - t
    t = time.monotonic()
    bstats = build.build_index_scale(
        g, str(path), eps=conf["plan"]["eps"], c=conf["plan"]["c"],
        seed=seed % (2**31 - 1))
    rec["setup"]["build_s"] = time.monotonic() - t
    for phase in ("d_wall_s", "hp_wall_s", "pack_wall_s"):
        rec["setup"][f"build.{phase}"] = bstats.get(phase)
    t = time.monotonic()
    idx = SlingIndex.load(str(path), mmap=True)
    rec["setup"]["load_s"] = time.monotonic() - t
    rec["index"] = {"n": idx.n, "m": g.m, "l_max": idx.plan.l_max,
                    "width": idx.hp.width, "entries": bstats["entries"],
                    "bytes": bstats["bytes"], "builder": bstats["builder"]}
    return src, dst, g, idx, bstats


def window(fe, rec: dict, spans, compiles, pauses, trace_dir,
           t_start: float, writer=None) -> None:
    """Drive the cell's traffic for ``rec["seconds"]`` (and the
    ``writer``'s batches), wait for what the window sent (open loop)
    and for the writer, and record requests, batches, spans, compiles,
    writes and the device's peak memory into ``rec``."""
    import jax

    from bench import traffic
    from bench import trace as trace_mod
    mix, seconds, n = rec["mix"], rec["seconds"], rec["index"]["n"]
    rng = np.random.default_rng([int(rec["seed"]), 1])
    if trace_dir is not None:
        trace_mod.start(str(trace_dir))
    compiles.on = spans.on = pauses.on = True
    t0 = time.monotonic()
    rec["setup_s"] = t0 - t_start
    drive = traffic.run_open if mix["loop"] == "open" else traffic.run_closed
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        if writer is not None:
            writer.start(t0)
        sent = drive(fe, mix, n, seconds, rng, t0)
        rest = t0 + seconds - time.monotonic()
        if rest > 0:
            time.sleep(rest)
    t_end = t0 + seconds
    spans.on = pauses.on = False
    if trace_dir is not None:
        jax.profiler.stop_trace()
    if mix["loop"] == "open":
        deadline = t_end + DRAIN_S
        for r in sent:
            try:
                r.ticket.result(timeout=max(0.0, deadline - time.monotonic()))
            except Exception:
                pass            # shed, failed or late: counted as lost
    if writer is not None:
        rec["writes"] = writer.join(t_end + DRAIN_S)
    compiles.on = False
    stats = jax.devices()[0].memory_stats() or {}
    rec["device"]["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    rec["window"] = {"t0": t0, "t_end": t_end, "drain_deadline": t_end + DRAIN_S}

    def answered(r):
        return r.ticket.done() and not r.ticket.shed
    rec["requests"] = [{
        "u": r.u, "v": r.v, "sched": r.sched, "sent": r.sent,
        "done": r.ticket.fulfil_t if answered(r) else None,
        "answer": r.ticket.result(timeout=0) if answered(r) else None}
        for r in sent]
    rec["batches"] = [
        {"kind": b.kind, "size": b.size, "cap": b.cap,
         "opened": b.opened, "closed": b.closed}
        for b in list(fe.batch_log) if t0 <= b.closed <= t_end]
    rec["spans"] = spans.spans
    rec["gc_pauses"] = [p for p in pauses.pauses if p[1] < t_end]
    rec["compiles_in_window"] = compiles.count


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, keep=None) -> dict:
    """One run; returns the run record. ``keep`` (the control) gets the
    record, the artifact, the edges and exact SimRank before they are
    dropped."""
    from bench import check, reference, traffic
    from bench import trace as trace_mod
    from repro.serve import FrontendConfig, ServeFrontend

    dev = device_facts(cell["chips"], require_tpu)
    if require_tpu:
        from bench import peaks
        dev_peaks = peaks.peaks(dev["kind"])
    else:
        dev_peaks = None
    enable_compile_cache()
    conf, mix = cell["config"], cell["mix"]
    batches = traffic.write_batches(mix, conf["graph"]["m"], seconds)
    rec = {"cell": cell["name"], "seed": seed, "seconds": seconds,
           "device": dev, "peaks": dev_peaks, "mix": mix, "setup": {}}
    WORK.mkdir(exist_ok=True)
    path = WORK / f"{cell['name']}-{os.getpid()}.sling"
    trace_dir = WORK / f"trace-{os.getpid()}"
    compiles = CompileCounter()
    pauses = GcPauses()
    epoch_paths = []
    writer = None
    try:
        hold_back = conf["graph"]["m"] - batches[0][1] if batches else 0
        src, dst, g, idx, _ = set_up(conf, seed, path, rec, hold_back)
        t = time.monotonic()
        fe = ServeFrontend(idx, g, FrontendConfig())
        try:
            spans = Spans(fe.engines, trace)
            if batches:
                writer = Writer(fe, idx, g, src, dst, batches, seed)
            warm_up(fe, mix, idx.n)
            # the build's garbage goes now, so that every window starts
            # from the same heap
            gc.collect()
            rec["setup"]["warmup_s"] = time.monotonic() - t
            window(fe, rec, spans, compiles, pauses,
                   trace_dir if trace else None, t_start, writer)
        finally:
            if writer is not None:
                writer.stop()
            fe.close()
        rec["engine"] = fe.stats()["per_replica"][0]
        rec["shapes"] = [list(s) for s in rec["engine"]["unique_shapes"]]
        rec["trace"] = (trace_mod.reduce_xplane(trace_mod.find_xplane(str(trace_dir)))
                        if trace else None)
        if writer is not None:
            writer.fe = writer.idx = None
        del fe, idx, spans         # the engines' device arrays go
        gc.collect()
        t = time.monotonic()
        base = len(src) - hold_back
        art = reference.read_artifact(str(path))
        edges = reference.Edges.of(src[:base], dst[:base], art.n, art.c)
        exact = reference.ExactSimRank(src[:base], dst[:base], art.n, art.c)
        rec["exact"] = {"seconds": time.monotonic() - t,
                        "steps": exact.steps, "bound": exact.bound}

        def epoch_refs(e):
            """Epoch e's index written to its own file and read back by
            the reference, with the graph after its first e batches."""
            epoch_paths.append(path.with_suffix(f".e{e}.sling"))
            writer.epochs[e - 1].save(str(epoch_paths[-1]))
            end = batches[e - 1][2]
            a = reference.read_artifact(str(epoch_paths[-1]))
            return (a, reference.Edges.of(src[:end], dst[:end], a.n, a.c),
                    reference.ExactSimRank(src[:end], dst[:end], a.n, a.c))

        refs = check.EpochRefs(epoch_refs, {0: (art, edges, exact)})
        rng_check = np.random.default_rng([int(seed), 3])
        rec["checks"] = check.compare(mix, rec["requests"], refs,
                                      cell["limits"], rng_check,
                                      rec.get("writes"))
        rec["check_s"] = time.monotonic() - t
        if writer is not None:
            swaps = check.swap_calls(rec["writes"])
            rec["answers_at_swap"] = sum(
                1 for r in rec["requests"] if r["done"] is not None
                and len(set(check.epochs_of(r["done"], swaps))) == 2)
            rec["epoch_check_s"] = dict(sorted(refs.seconds.items()))
        if keep is not None:
            keep(rec, art, edges, exact)
        return rec
    finally:
        pauses.close()
        path.unlink(missing_ok=True)
        for p in epoch_paths:
            p.unlink(missing_ok=True)
        if trace:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)


def result_line(cell: dict, rec: dict, trace: bool) -> dict:
    """The last line: the contract's keys, the checks last."""
    specs = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in specs:
        value = metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    reqs = rec["requests"]
    failed = sum(r["done"] is None for r in reqs)
    checks = rec["checks"]
    line = {
        "correct": bool(all(c["value"] <= c["limit"] for c in checks.values())),
        "attempted": len(reqs),
        "failed": failed,
        "metrics": metrics,
        "device": dict(rec["device"]),
    }
    if trace and rec["trace"] is not None:
        tr = rec["trace"]
        line["device"]["busy_s"] = tr["busy_s"]
        line["device"]["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    return line


def print_result(line: dict, rec: dict) -> None:
    """Facts on stdout, the compared numbers as the last lines of
    stderr, the result as the last line of stdout."""
    gc2 = [p[2] for p in rec.get("gc_pauses", []) if p[0] == 2]
    facts = {"setup": rec["setup"], "index": rec.get("index"),
             "compiles_in_window": rec.get("compiles_in_window"),
             "batches_in_window": len(rec.get("batches", [])),
             "gc_in_window": {"collections": len(rec.get("gc_pauses", [])),
                              "gen2": len(gc2),
                              "gen2_max_ms": 1e3 * max(gc2, default=0.0),
                              "gen2_sum_ms": 1e3 * sum(gc2)},
             "exact": rec.get("exact"), "check_s": rec.get("check_s")}
    if "writes" in rec:
        # times as seconds from the window's start
        t0 = rec["window"]["t0"]
        facts.update(writes=[{k: v - t0 if k in WRITE_TIMES else v
                              for k, v in w.items()} for w in rec["writes"]],
                     answers_at_swap=rec.get("answers_at_swap"),
                     epoch_check_s=rec.get("epoch_check_s"))
    print("facts " + json.dumps(facts), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
