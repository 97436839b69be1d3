"""Pallas kernels for the SLING hot paths.

:func:`interpret_mode` is the one place that decides whether a Pallas
kernel runs compiled or through the interpreter. Kernel entry points
take ``interpret`` as a required argument (no default), and every
caller passes ``interpret_mode()``, so on a TPU a kernel is always
compiled by Mosaic and never silently interpreted.
"""
from __future__ import annotations


def interpret_mode() -> bool:
    """True unless the default JAX backend is a TPU: Pallas TPU kernels
    compile only for the chip; everywhere else they run interpreted
    (CPU tests)."""
    import jax
    return jax.default_backend() != "tpu"
