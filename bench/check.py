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

``topk_score_err`` and ``pair_abs_err`` hold the serving path to the
artifact it loaded; ``simrank_err`` holds the artifact, and so the
build, to the guarantee.
"""
from __future__ import annotations

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


def compare(mix: dict, requests: list, art, edges, exact, limits: dict,
            rng) -> dict:
    reqs = sample(requests, int(mix["check_samples"]), rng)
    got = {"lost": sum(r["done"] is None for r in requests)}
    if mix["kind"] == "topk":
        got.update(topk_numbers(reqs, int(mix["k"]), art, edges, exact))
    else:
        got.update(pair_numbers(reqs, art, exact))
    if not reqs:
        got["no_answer_to_check"] = 1
    return {name: {"value": value, "limit": limits.get(name, 0)}
            for name, value in got.items()}
