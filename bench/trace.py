"""Reduce a profiler trace (``.xplane.pb``) to the device numbers.

The benchmark wraps its measured window in a host annotation named
``bench.window`` and each call into the engine in ``bench.engine.*``
annotations (``jax.profiler.TraceAnnotation``); host and device events
share the trace's clock. From the device planes (``/device:TPU:<i>``)
this reads:

* ``busy_s``: the union of the intervals in which an operation
  (line ``XLA Ops``) ran, inside the window, averaged over the chips
  that ran anything;
* ``modules``: per XLA module (line ``XLA Modules``), keyed by the
  jitted function's name (``jit_<name>(<id>)`` -> ``<name>``), the
  number of executions and their summed device seconds;
* ``device_ops``: the ten operations with the most device time;
* ``idle_gaps``: the ten longest gaps between operations inside the
  window, each named by the host annotation that overlaps it most
  (``host: none`` where the benchmark had no call in flight).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
HOST_PREFIX = "bench."


def start(trace_dir: str) -> None:
    """Start the profiler without the Python call tracer: host
    annotations and device events only, so tracing costs little."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def module_key(name: str) -> str:
    """``jit_batched_topk(123)`` -> ``batched_topk``."""
    name = re.sub(r"\(\d+\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_label(name: str) -> str:
    """An HLO op's text without its layouts, cut to 200 characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:200]


def union(intervals):
    """Merge (start, end) pairs; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    host_spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name.upper():
            lines = {line.name: list(line.events) for line in plane.lines}
            devices.append(lines)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(HOST_PREFIX):
                    host_spans.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} host annotation")
    lo, hi = window
    busy, modules, ops, gaps = [], {}, {}, []
    for lines in devices:
        op_iv = [(e.start_ns, e.start_ns + e.duration_ns)
                 for e in lines.get("XLA Ops", [])]
        merged = union(_clip(op_iv, lo, hi))
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged))
        for e in lines.get("XLA Ops", []):
            if lo <= e.start_ns < hi:
                label = op_label(e.name)
                ops[label] = ops.get(label, 0.0) + e.duration_ns * 1e-9
        for e in lines.get("XLA Modules", []):
            if lo <= e.start_ns < hi:
                m = modules.setdefault(module_key(e.name),
                                       {"count": 0, "total_s": 0.0})
                m["count"] += 1
                m["total_s"] += e.duration_ns * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy:
        return {"busy_s": 0.0, "window_s": (hi - lo) * 1e-9, "chips": 0,
                "modules": {}, "device_ops": [], "idle_gaps": []}

    def label(s, e):
        best, name = 0, "host: none"
        for hs, he, hn in host_spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, "host: " + hn
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "chips": len(busy),
        "modules": modules,
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[label(s, e), (e - s) * 1e-9] for s, e in gaps[:10]],
    }
