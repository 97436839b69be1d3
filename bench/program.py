"""What the program's own spans say about a run.

``repro.serve.spans`` records the serving path's spans (name, id,
parent, start, end, thread, attrs) on the frontend's clock, and in a
profiler trace each is also a host annotation of the same name. This
module reduces both:

* from the records and the window's requests: each request's latency
  cut into parts that tile it (:func:`request_parts`), the frontend's
  queue wait and hand-off, the engine's host time around the device,
  the worker's time between engine calls, and what the batch and
  engine spans' attributes say of batching, the LRU and padding;
* from the frontend's ``batch_log``: the hand-off per batch;
* from a trace (``.xplane.pb``): the device's idle gaps in the window,
  each named by the innermost ``sling.*`` annotation that covers most
  of it (:func:`label`), and the idle seconds per innermost span
  (:func:`idle_by_span`). A span still open when the profiler stops
  is not in the trace, so the window's last gap may be covered only
  in part.
"""
from __future__ import annotations

import numpy as np

BATCH = "sling.frontend.batch"
SYNC = "sling.engine.sync"
WAIT = "sling.worker.wait"
PREFIX = "sling."
TOL_S = 1e-6            # a tiling that misses by more is a violation


def _ms_median(xs):
    return 1e3 * float(np.median(xs)) if len(xs) else None


def named(records, name: str, lo=-np.inf, hi=np.inf) -> list:
    """The records of span ``name`` that began in [lo, hi]."""
    return [r for r in records if r[0] == name and lo <= r[3] <= hi]


def request_parts(requests, records) -> list:
    """Per request (a dict with ``sched``, ``sent``, ``admit``, ``id``
    and ``done``, None if unanswered): its latency ``done - sched`` cut
    into (sent - sched, admit - sent, close - admit, batch start -
    close, done - batch start), the close and start read from the batch
    span that lists its id; None where it was not answered or no batch
    lists it."""
    batch = {}
    for r in records:
        if r[0] == BATCH:
            for rid in r[6]["requests"]:
                batch[rid] = (r[6]["closed"], r[3])
    out = []
    for q in requests:
        b = batch.get(q["id"])
        if q["done"] is None or b is None:
            out.append(None)
            continue
        closed, start = b
        out.append((q["sent"] - q["sched"], q["admit"] - q["sent"],
                    closed - q["admit"], start - closed, q["done"] - start))
    return out


def tiling_violations(requests, parts) -> int:
    """Answered requests whose parts are missing, negative, or do not
    add up to their latency within :data:`TOL_S`."""
    bad = 0
    for q, p in zip(requests, parts):
        if q["done"] is None:
            continue
        if p is None or min(p) < -TOL_S \
                or abs(sum(p) - (q["done"] - q["sched"])) > TOL_S:
            bad += 1
    return bad


def queue_wait_ms(parts):
    """Median close - admission over the answered requests."""
    return _ms_median([p[2] for p in parts if p is not None])


def handoff_ms(parts):
    """Median batch start - close over the answered requests."""
    return _ms_median([p[3] for p in parts if p is not None])


def engine_host_ms(records, kind: str, lo, hi):
    """Median over the ``sling.engine.<kind>`` calls begun in [lo, hi]
    of the call's time less its ``sling.engine.sync`` children."""
    sync: dict = {}
    for r in records:
        if r[0] == SYNC:
            sync[r[2]] = sync.get(r[2], 0.0) + (r[4] - r[3])
    return _ms_median([(r[4] - r[3]) - sync.get(r[1], 0.0)
                       for r in named(records, f"sling.engine.{kind}",
                                      lo, hi)])


def worker_gap_ms(records, kind: str, lo, hi):
    """Median, over consecutive ``sling.engine.<kind>`` calls on one
    thread, both begun in [lo, hi], of the time from one call's end to
    the next one's start less the thread's ``sling.worker.wait`` time
    between them."""
    gaps = []
    threads = {r[5] for r in named(records, f"sling.engine.{kind}", lo, hi)}
    for th in threads:
        calls = sorted(r[3:5] for r in named(
            records, f"sling.engine.{kind}", lo, hi) if r[5] == th)
        waits = sorted(r[3:5] for r in records
                       if r[0] == WAIT and r[5] == th)
        for (_, a), (b, _) in zip(calls, calls[1:]):
            waited = sum(max(0.0, min(e, b) - max(s, a)) for s, e in waits
                         if s < b and e > a)
            gaps.append(b - a - waited)
    return _ms_median(gaps)


def batch_summary(records, lo, hi):
    """What the ``sling.frontend.batch`` spans begun in [lo, hi] say of
    batching: their count, the median fill (``size`` over ``cap``, %),
    the median count of batches ahead of each on its replica when it
    closed (``ahead``, itself included while it waits), the share of
    each close ``reason`` (%), and the batches per ``kind`` and per
    ``replica``; None where there is none."""
    attrs = [r[6] for r in named(records, BATCH, lo, hi)]
    if not attrs:
        return None

    def count(key):
        out: dict = {}
        for a in attrs:
            out[str(a[key])] = out.get(str(a[key]), 0) + 1
        return out
    return {
        "batches": len(attrs),
        "fill_pct_median": 100 * float(np.median(
            [a["size"] / a["cap"] for a in attrs])),
        "ahead_median": float(np.median([a["ahead"] for a in attrs])),
        "reason_pct": {k: 100 * v / len(attrs)
                       for k, v in sorted(count("reason").items())},
        "kinds": count("kind"),
        "replicas": count("replica"),
    }


def engine_summary(records, kind: str, lo, hi):
    """Over the ``sling.engine.<kind>`` calls begun in [lo, hi]: the
    calls, the requests, the share of requests the LRU missed
    (``misses`` over ``requests``, %) and the share of the slots sent to
    the device that were padding (``pad`` over ``misses`` + ``pad``,
    %); None where there is none."""
    attrs = [r[6] for r in named(records, f"sling.engine.{kind}", lo, hi)]
    if not attrs:
        return None
    req = sum(a["requests"] for a in attrs)
    miss = sum(a["misses"] for a in attrs)
    pad = sum(a["pad"] for a in attrs)
    return {"calls": len(attrs), "requests": req,
            "miss_pct": 100 * miss / req if req else None,
            "pad_pct": 100 * pad / (miss + pad) if miss + pad else None}


def batch_log_handoff_ms(batch_log, lo, hi):
    """Median ``started - closed`` (ms) over the ``batch_log`` entries
    closed in [lo, hi]: the hand-off per batch, which the frontend keeps
    with the recorder off too."""
    return _ms_median([b.started - b.closed for b in batch_log
                       if lo <= b.closed <= hi])


# ----------------------------------------------------------------------
# device idle time by host span
# ----------------------------------------------------------------------
def timeline(path: str, window_name: str = "bench.window") -> dict:
    """From a trace: the window (ns), each TPU device's merged busy
    intervals in it, and the host annotations named ``sling.*`` or
    ``bench.*`` (start, end, name)."""
    from jax.profiler import ProfileData

    from bench.trace import union
    pd = ProfileData.from_file(path)
    window, host, devices = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name.upper():
            devices.append([(e.start_ns, e.start_ns + e.duration_ns)
                            for line in plane.lines if line.name == "XLA Ops"
                            for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == window_name:
                        window = span[:2]
                    elif e.name.startswith((PREFIX, "bench.")):
                        host.append(span)
    if window is None:
        raise ValueError(f"{path}: no {window_name!r} host annotation")
    lo, hi = window
    busy = [union([(max(s, lo), min(e, hi)) for s, e in ops
                   if e > lo and s < hi]) for ops in devices]
    return {"window": window, "busy": [b for b in busy if b],
            "host": sorted(host)}


def gaps(busy, lo, hi) -> list:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def label(s, e, host) -> str:
    """The innermost (shortest) ``sling.*`` span among those covering
    more than half of [s, e]; else the ``sling.*`` span, then the
    ``bench.*`` span, overlapping it most; else ``host: none``."""
    inner, most = None, {True: (0, None), False: (0, None)}
    for hs, he, name in host:
        ov = min(e, he) - max(s, hs)
        if ov <= 0:
            continue
        ours = name.startswith(PREFIX)
        if ours and 2 * ov > e - s and (inner is None
                                        or he - hs < inner[0]):
            inner = (he - hs, name)
        if ov > most[ours][0]:
            most[ours] = (ov, name)
    name = inner[1] if inner else most[True][1] or most[False][1]
    return "host: " + (name or "none")


def idle_by_span(idle, host) -> dict:
    """Seconds of the idle intervals ``idle`` (ns, sorted, disjoint)
    per innermost ``sling.*`` span at each instant (``none`` where no
    such span is open)."""
    spans = sorted(h for h in host if h[2].startswith(PREFIX))
    out: dict = {}
    i, active = 0, []
    for s, e in idle:
        while i < len(spans) and spans[i][0] < e:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[1] > s]
        cuts = sorted({s, e} | {x for h in active for x in h[:2]
                                if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            cover = [h for h in active if h[0] <= a and h[1] >= b]
            name = (min(cover, key=lambda h: h[1] - h[0])[2]
                    if cover else "none")
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def idle(path: str) -> dict:
    """The ten longest idle gaps of the window, labelled by
    :func:`label`, and :func:`idle_by_span` summed over the window,
    averaged over the chips that ran anything."""
    tl = timeline(path)
    lo, hi = tl["window"]
    all_gaps, by_span = [], {}
    for busy in tl["busy"]:
        g = gaps(busy, lo, hi)
        all_gaps += g
        for name, secs in idle_by_span(g, tl["host"]).items():
            by_span[name] = by_span.get(name, 0.0) + secs / len(tl["busy"])
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {"idle_gaps": [[label(s, e, tl["host"]), (e - s) * 1e-9]
                          for s, e in all_gaps[:10]],
            "idle_by_span": dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1]))}
