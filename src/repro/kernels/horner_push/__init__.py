"""Pallas-fused Horner-push kernel + backend selection.

The one hot loop behind every SLING query path (single-source, top-k,
the bulk join, and the sharded fan-out) is the Horner-stacked push slab
routine (:func:`repro.core.single_source.horner_push`). This package
provides a Pallas TPU kernel that fuses its per-step edge-gather/SpMV,
tau-prune, and Horner seed-accumulate into one grid program
(DESIGN.md section 11), plus the process-wide backend switch the
serving/join layers consult:

  * ``set_push_backend("lax" | "pallas" | "auto")`` / environment
    variable ``SLING_PUSH_BACKEND`` -- "auto" resolves to "lax" on
    every platform: the fused kernel's in-kernel frontier row gather is
    refused by the v5e compiler, so it is selected only explicitly (and
    on a TPU then fails at compile time);
  * ``resolve_push_backend(name)`` -- resolve a config value ("auto"
    defers to the process switch);
  * ``use_push_backend(name)`` -- context manager for tests.

The lax path stays as the reference implementation and remains the
backend of the bf16-frontier pod push (its gather converts dtypes
between prune and push, which the fused kernel deliberately does not
model -- see DESIGN.md section 11).
"""
from __future__ import annotations

import contextlib
import os

_VALID = ("auto", "lax", "pallas")
_backend = os.environ.get("SLING_PUSH_BACKEND", "auto")


def set_push_backend(name: str) -> None:
    """Set the process-wide Horner-push backend switch."""
    global _backend
    if name not in _VALID:
        raise ValueError(f"push backend {name!r} not in {_VALID}")
    _backend = name


def push_backend() -> str:
    """The resolved process-wide backend ("lax" or "pallas")."""
    return resolve_push_backend(_backend)


def resolve_push_backend(name: str | None = None) -> str:
    """Resolve a config value to a concrete backend.

    ``None``/"auto" defer to the process switch; a process switch of
    "auto" resolves to lax on every platform (the fused kernel does not
    compile for a TPU yet; off the chip it runs only interpreted).
    """
    name = name or "auto"
    if name not in _VALID:
        raise ValueError(f"push backend {name!r} not in {_VALID}")
    if name == "auto":
        name = _backend
    return "lax" if name == "auto" else name


@contextlib.contextmanager
def use_push_backend(name: str):
    """Temporarily pin the process-wide backend (tests/benchmarks)."""
    global _backend
    prev = _backend
    set_push_backend(name)
    try:
        yield
    finally:
        _backend = prev


from repro.kernels.horner_push.ops import (  # noqa: E402
    block_align_edges, horner_push_pallas, push_cost_model)

__all__ = [
    "set_push_backend", "push_backend", "resolve_push_backend",
    "use_push_backend", "block_align_edges", "horner_push_pallas",
    "push_cost_model",
]
