"""The one traffic generator. A mix is a data file under
``bench/traffic/<name>.json``; this module turns it and a seed into
requests, and drives them through ``ServeFrontend``.

Mix keys:

* ``kind`` and ``loop``: ``"topk"`` requests in a ``"closed"`` loop,
  or ``"pair"`` requests in an ``"open"`` one.
* closed loop: ``clients``, each sends its next request as soon as its
  answer is back.
* open loop: ``rate_per_s`` and ``arrivals`` (``"poisson"``): the
  gaps are the exponential quantiles (i + 1/2) / N of the rate, shuffled
  by the seed, so every seed sends the same number of requests over
  the same span, in another order.
* ``nodes``: ``{"dist": "uniform"}`` or ``{"dist": "zipf", "s": ...}``
  for the query nodes; a pair draws both its ends from it.
* ``k`` (top-k).
* ``check_samples``: how many answers of the window are compared
  with the reference.
* ``writes`` (optional): graph writes during the window,
  ``{"hold_back": h, "batches": b, "first_at_s": f, "every_s": s}``.
  The last ``h`` edges of the configuration's draw, in
  ``graphs.make_edges`` order, are left out of the graph that is
  built; they arrive as inserts in ``b`` equal consecutive batches,
  batch i due ``f + i * s`` seconds after the window starts, so that
  at the end the graph is the configuration's own and every seed does
  the same writes. Refused: ``h`` not in [1, m) or not a multiple of
  ``b``, more than ``MAX_WRITE_BATCHES`` batches (each epoch is
  checked against its own exact SimRank), a last batch due at or
  after the window's end. Deletions are not drawn.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

MAX_WRITE_BATCHES = 4


def zipf_weights(n: int, s: float) -> np.ndarray:
    """Zipf(s) pmf over n ranks: p(rank r) ~ r^-s, r = 1..n."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    return w / w.sum()


def zipf_nodes(n: int, size: int, s: float,
               rng: np.random.Generator) -> np.ndarray:
    """``size`` node ids drawn Zipf(s); a seeded permutation assigns
    ranks to nodes, so "hot" does not mean "low id"."""
    ranks_to_node = rng.permutation(n)
    draws = rng.choice(n, size=int(size), p=zipf_weights(n, s))
    return ranks_to_node[draws].astype(np.int32)


def draw_nodes(n: int, size: int, spec: dict,
               rng: np.random.Generator) -> np.ndarray:
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return rng.integers(0, n, size, dtype=np.int64).astype(np.int32)
    if dist == "zipf":
        return zipf_nodes(n, size, spec["s"], rng)
    raise ValueError(f"unknown node distribution {dist!r}")


def pair_requests(n: int, size: int, mix: dict,
                  rng) -> tuple[np.ndarray, np.ndarray]:
    spec = mix.get("nodes", {})
    return draw_nodes(n, size, spec, rng), draw_nodes(n, size, spec, rng)


def open_schedule(rate: float, seconds: float, rng) -> np.ndarray:
    """Send offsets in [0, seconds): N = rate * seconds arrivals whose
    gaps are the N exponential quantiles of the rate, shuffled."""
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    # the quantiles' mean is just under 1 / rate, so all fit
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def write_batches(mix: dict, m: int,
                  seconds: float) -> list[tuple[float, int, int]]:
    """The mix's write batches as (due offset from the window's start,
    first edge, end edge) slices of the configuration's ``m`` edges;
    [] for a mix without writes."""
    w = mix.get("writes")
    if w is None:
        return []
    hold, count = int(w["hold_back"]), int(w["batches"])
    first, every = float(w["first_at_s"]), float(w["every_s"])
    if not 1 <= count <= MAX_WRITE_BATCHES:
        raise ValueError(f"writes: {count} batches, not 1 to "
                         f"{MAX_WRITE_BATCHES}")
    if not 1 <= hold < m or hold % count:
        raise ValueError(f"writes: hold_back {hold} must lie in [1, {m}) "
                         f"and split into {count} equal batches")
    if first < 0 or (count > 1 and every <= 0):
        raise ValueError("writes: first_at_s must be >= 0 and every_s > 0")
    last = first + (count - 1) * every
    if last >= seconds:
        raise ValueError(f"writes: the last batch is due at {last} s, "
                         f"not inside the {seconds}-s window")
    size, base = hold // count, m - hold
    return [(first + i * every, base + i * size, base + (i + 1) * size)
            for i in range(count)]


@dataclasses.dataclass
class Sent:
    """One request of the window."""
    kind: str
    u: int
    v: int
    sched: float          # when it was due (monotonic seconds)
    sent: float           # when it was submitted
    ticket: object


def submit(fe, mix: dict, u: int, v: int):
    if mix["kind"] == "topk":
        return fe.submit_topk(int(u), int(mix["k"]))
    if mix["kind"] == "pair":
        return fe.submit_pair(int(u), int(v))
    raise ValueError(f"unknown request kind {mix['kind']!r}")


def run_open(fe, mix: dict, n: int, seconds: float, rng,
             t0: float) -> list[Sent]:
    """Send pairs on the schedule from ``t0`` on; return every request
    sent."""
    if mix["kind"] != "pair":
        raise ValueError("the open loop sends pairs")
    offsets = open_schedule(float(mix["rate_per_s"]), seconds, rng)
    us, vs = pair_requests(n, len(offsets), mix, rng)
    sent = []
    for off, u, v in zip(offsets.tolist(), us.tolist(), vs.tolist()):
        due = t0 + off
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        now = time.monotonic()
        sent.append(Sent(mix["kind"], u, v, due, now, submit(fe, mix, u, v)))
    return sent


def run_closed(fe, mix: dict, n: int, seconds: float, rng,
               t0: float) -> list[Sent]:
    """``clients`` top-k callers from ``t0`` until ``t0 + seconds``;
    return the requests answered inside the window. Batches are served
    in the order they formed, so waiting on the oldest outstanding
    request sees answers in the order they come."""
    if mix["kind"] != "topk":
        raise ValueError("the closed loop sends top-k requests")
    end = t0 + seconds
    pool = collections.deque()

    def next_request():
        if not pool:
            pool.extend(draw_nodes(n, 4096, mix.get("nodes", {}), rng).tolist())
        u = pool.popleft()
        now = time.monotonic()
        return Sent(mix["kind"], u, u, now, now, submit(fe, mix, u, u))

    outstanding = collections.deque(next_request()
                                    for _ in range(int(mix["clients"])))
    done = []
    while outstanding:
        r = outstanding[0]
        left = end - time.monotonic()
        if left <= 0:
            break
        try:
            r.ticket.result(timeout=left)
        except TimeoutError:
            break
        except Exception:
            # shed or failed: the client counts it and sends the next
            pass
        if r.ticket.fulfil_t is None or r.ticket.fulfil_t > end:
            break
        outstanding.popleft()
        done.append(r)
        outstanding.append(next_request())
    # answered by the window's end but not yet looked at
    done += [r for r in outstanding
             if r.ticket.done() and r.ticket.fulfil_t <= end]
    return done
