"""The comparison that decides ``correct``.

Once the window has closed, a sample of its answered requests, drawn
from the seed, is recomputed by the plain references of
``bench/reference.py`` and each compared number is held to its limit
in ``bench/limits/<cell>.json``:

* ``lost``: requests of the window that never got an answer (shed,
  failed, or later than the drain); limit 0.
* ``malformed`` (top-k): answers that are not k distinct in-range ids
  with finite, descending scores; limit 0.
* ``topk_score_err``: the widest gap between a served score and the
  reference's score of the same node, or by which the reference's
  score of the i-th served node lies below the reference's i-th best
  score (a node ranked where it does not belong).
* ``pair_abs_err``: the widest gap between a served pair score and the
  reference's.
* ``simrank_err``: the widest gap between a served answer and exact
  SimRank of the edge list (:class:`reference.ExactSimRank`), which
  shares nothing with the build. For top-k it also counts a node left
  out: no node outside the answer may exceed the k-th served score by
  more than eps. Its limit is the configuration's eps (Theorem 1).

* ``write_failed`` (a mix with writes): write batches that raised or
  did not finish; limit 0.

``topk_score_err`` and ``pair_abs_err`` hold the serving path to the
artifact it loaded; ``simrank_err`` holds the artifact, and so the
build, to the guarantee.

With writes, the graph and index change during the window, one epoch
per applied batch, and each answer is held to the epoch that served
it. The frontend's swap drains every old-epoch batch before any
replica swaps, so an answer fulfilled before swap e's call started is
epoch e - 1, one fulfilled after it returned is epoch e, and one
fulfilled during the call may be either: it passes if it meets every
limit against one of the two.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference


def sample(requests: list, count: int, rng) -> list:
    answered = [r for r in requests if r["done"] is not None]
    if len(answered) <= count:
        return answered
    pick = rng.choice(len(answered), size=count, replace=False)
    return [answered[i] for i in sorted(pick)]


def topk_numbers(reqs: list, k: int, art, edges, exact) -> dict:
    k = min(k, art.n)
    malformed, score_err, sim_err = 0, 0.0, 0.0
    for r in reqs:
        sv, si = (np.asarray(x) for x in r["answer"])
        row = reference.horner_row(art, edges, r["u"])
        if (sv.shape != (k,) or si.shape != (k,)
                or not np.all(np.isfinite(sv))
                or si.min() < 0 or si.max() >= art.n
                or len(np.unique(si)) != k or np.any(np.diff(sv) > 0)):
            malformed += 1
            continue
        best, _ = reference.topk_of(row, k)
        score_err = max(score_err, float(np.abs(sv - row[si]).max()),
                        float((best - row[si]).max()))
        truth = exact.row(r["u"])
        left_out = np.delete(truth, si)
        sim_err = max(sim_err, float(np.abs(sv - truth[si]).max()),
                      float(left_out.max(initial=0.0) - sv[-1]))
    return {"malformed": malformed, "topk_score_err": score_err,
            "simrank_err": sim_err}


def pair_numbers(reqs: list, art, exact) -> dict:
    err, sim_err = 0.0, 0.0
    for r in reqs:
        served = float(r["answer"])
        err = max(err, abs(served - reference.pair(art, r["u"], r["v"])))
        sim_err = max(sim_err, abs(served - exact.pair(r["u"], r["v"])))
    return {"pair_abs_err": err, "simrank_err": sim_err}


class EpochRefs:
    """The references of each epoch of a run, made on first use.
    ``make(e)`` returns epoch e's (artifact, edges, exact SimRank);
    ``seconds[e]`` sums the time spent making them and comparing
    answers with them."""

    def __init__(self, make, made=None):
        self._make = make
        self._refs = dict(made or {})
        self.seconds: dict[int, float] = {}

    def numbers(self, mix: dict, reqs: list, e: int) -> dict:
        t = time.monotonic()
        if e not in self._refs:
            self._refs[e] = self._make(e)
        art, edges, exact = self._refs[e]
        if mix["kind"] == "topk":
            got = topk_numbers(reqs, int(mix["k"]), art, edges, exact)
        else:
            got = pair_numbers(reqs, art, exact)
        self.seconds[e] = self.seconds.get(e, 0.0) + time.monotonic() - t
        return got


def swap_calls(writes) -> list[tuple[float, float]]:
    """(start, end) of each swap call that returned, in epoch order."""
    return [(w["swap_start"], w["swap_end"]) for w in writes or ()
            if w.get("applied")]


def epochs_of(done: float, swaps) -> tuple[int, int]:
    """The first and last epoch that may have served an answer
    fulfilled at ``done``."""
    lo = hi = 0
    for e, (start, end) in enumerate(swaps, 1):
        if done < start:
            break
        hi = e
        if done > end:
            lo = e
    return lo, hi


def _merge(parts) -> dict:
    out = {}
    for got in parts:
        for name, value in got.items():
            out[name] = (out.get(name, 0) + value if name == "malformed"
                         else max(out.get(name, 0.0), value))
    return out


def _passes(got: dict, limits: dict) -> bool:
    return all(value <= limits.get(name, 0) for name, value in got.items())


def compare(mix: dict, requests: list, refs: EpochRefs, limits: dict,
            rng, writes=None) -> dict:
    """The compared numbers, each with its limit. ``writes`` are the
    run's write records (None for a mix without writes)."""
    reqs = sample(requests, int(mix["check_samples"]), rng)
    got = {"lost": sum(r["done"] is None for r in requests)}
    swaps = swap_calls(writes)
    groups, either = {0: []}, []
    for r in reqs:
        lo, hi = epochs_of(r["done"], swaps)
        if lo == hi:
            groups.setdefault(lo, []).append(r)
        else:
            either.append((r, lo, hi))
    parts = [refs.numbers(mix, group, e) for e, group in sorted(groups.items())]
    for r, lo, hi in either:
        tried = [refs.numbers(mix, [r], e) for e in range(lo, hi + 1)]
        parts.append(next((t for t in tried if _passes(t, limits)),
                          tried[-1]))
    got.update(_merge(parts))
    if writes is not None:
        got["write_failed"] = sum(not w.get("applied") for w in writes)
    if not reqs:
        got["no_answer_to_check"] = 1
    return {name: {"value": value, "limit": limits.get(name, 0)}
            for name, value in got.items()}
