"""Shared arithmetic of the metric readers. Each reader is
``bench/metrics/<metric>.py`` with ``read(record) -> float | None``;
None leaves the metric out of the result line."""
from __future__ import annotations

import numpy as np


def latencies_ms(rec: dict, kind: str):
    """Per request sent in the window: answer time minus the time it
    was due, in ms. A request never answered counts until the drain's
    end, a lower bound on its latency."""
    if rec["mix"]["kind"] != kind or not rec["requests"]:
        return None
    end = rec["window"]["drain_deadline"]
    return np.array([((r["done"] if r["done"] is not None else end)
                      - r["sched"]) * 1e3 for r in rec["requests"]])


def batch_fill(rec: dict, kind: str):
    """Mean size/cap, in %, of the window's batches that the frontend's
    ``batch_log`` ring still holds (its newest ``log_cap``)."""
    b = [x["size"] / x["cap"] for x in rec["batches"] if x["kind"] == kind]
    return 100.0 * float(np.mean(b)) if b else None


def module_ms(rec: dict, word: str):
    """Mean device ms per execution of the jitted programs whose name
    holds ``word`` (``batched_topk`` and its Pallas twin hold "topk";
    ``pair_query_batch_pallas`` and ``_pair_query_batch`` hold "pair")."""
    tr = rec.get("trace")
    if not tr:
        return None
    mods = [m for k, m in tr["modules"].items() if word in k]
    count = sum(m["count"] for m in mods)
    if not count:
        return None
    return 1e3 * sum(m["total_s"] for m in mods) / count


def idle_share(rec: dict, kind: str):
    tr = rec.get("trace")
    if rec["mix"]["kind"] != kind or not tr or not tr["chips"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

