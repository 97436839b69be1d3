"""Span recorder for the serving path, off by default.

At most one :class:`Recorder` is enabled in a process at a time
(:func:`enable` / :func:`disable`). While none is, every instrumented
boundary costs one read of this module's ``_rec`` and returns the shared
no-op span :data:`OFF`: no allocation, no profiler annotation, no
listener. While one is, each span records a tuple

    (name, id, parent id, start, end, thread ident, attrs)

on the recorder's clock (the frontend's: ``MonotonicClock.now``, or a
``VirtualClock`` in tests), and is also a
``jax.profiler.TraceAnnotation`` of the same name, so that a profiler
trace shows it in its host plane on the device trace's clock. A span
begins and ends on one thread; its parent is the span open on that
thread when it began.

The spans (see serve/frontend.py and serve/engine.py):

* ``sling.frontend.timer``: one batch-close timer callback;
* ``sling.worker.wait``: a dispatch worker blocked on its queue;
* ``sling.frontend.batch``: one batch on its replica (attrs: kind, size,
  cap, reason, replica, ahead, closed, requests);
* ``sling.frontend.fulfil``: its tickets fulfilled and ``batch_log``;
* ``sling.engine.pairs`` / ``sling.engine.topk`` /
  ``sling.engine.sources``: one engine call (attrs: requests, misses,
  pad; top-k and sources also ``push``, the engine's push path,
  "dense" or "sparse");
* ``sling.engine.cache``: LRU lookups or puts;
* ``sling.engine.pad``: padding and upload of the id batches;
* ``sling.engine.launch``: the jitted call until it returns (attr
  ``compiles``: programs compiled or loaded inside it);
* ``sling.engine.sync``: the wait for the device and the copy back.

A request's parts join through its ``Ticket.id``, which the batch span
lists under ``requests``.
"""
from __future__ import annotations

import itertools
import threading

import jax

# one per request for a new executable, where the persistent
# compilation cache is on (the serving CLI and the benchmark turn it on)
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
LAUNCH = "sling.engine.launch"

_rec: "Recorder | None" = None


class Recorder:
    """Span records in memory: at most ``cap``, later ones counted in
    ``dropped``."""

    def __init__(self, now, cap: int = 1 << 20):
        self.now = now
        self.cap = cap
        self.records: list[tuple] = []
        self.dropped = 0
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._local = threading.local()

    def open_spans(self) -> list:
        """The calling thread's open spans, outermost first."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add(self, record: tuple) -> None:
        with self._lock:
            if len(self.records) < self.cap:
                self.records.append(record)
            else:
                self.dropped += 1


class Span:
    """One open span of an enabled recorder; true in a boolean test."""

    __slots__ = ("rec", "name", "id", "parent", "start", "attrs", "_ann")

    def __init__(self, rec: Recorder, name: str):
        self.rec = rec
        self.name = name
        self.attrs: dict = {}

    def note(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = self.rec.open_spans()
        self.parent = stack[-1].id if stack else None
        self.id = next(self.rec._ids)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = self.rec.now()
        return self

    def __exit__(self, *exc) -> None:
        end = self.rec.now()
        self._ann.__exit__(*exc)
        self.rec.open_spans().pop()
        self.rec._add((self.name, self.id, self.parent, self.start, end,
                       threading.get_ident(), self.attrs))


class _Off:
    """The span of a disabled recorder: does nothing, false in a
    boolean test (so ``if sp: sp.note(...)`` costs nothing when off)."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def __bool__(self) -> bool:
        return False


OFF = _Off()


def span(name: str):
    """A context manager for the span ``name``: :data:`OFF` while no
    recorder is enabled."""
    rec = _rec
    if rec is None:
        return OFF
    return Span(rec, name)


def _on_event(event: str, **_kw) -> None:
    """Charge a compile to the calling thread's open launch span."""
    rec = _rec
    if rec is None or event != COMPILE_EVENT:
        return
    for sp in reversed(rec.open_spans()):
        if sp.name == LAUNCH:
            sp.attrs["compiles"] = sp.attrs.get("compiles", 0) + 1
            return


def enable(rec: Recorder) -> None:
    """Record every span of the process into ``rec`` from now on."""
    global _rec
    if _rec is None:
        jax.monitoring.register_event_listener(_on_event)
    _rec = rec


def disable() -> None:
    """Stop recording; the recorder keeps what it holds."""
    global _rec
    if _rec is not None:
        jax.monitoring.unregister_event_listener(_on_event)
    _rec = None
