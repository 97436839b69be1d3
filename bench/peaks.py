"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
at 819 GB/s. A device that is not in the table is an error, never a
default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       "with their source") from None


def topk_least_bytes(batch: int, n: int, m: int, l_max: int) -> int:
    """Least HBM bytes of one batched Horner push plus top-k.

    Each of the l_max push steps reads every edge's source, destination
    and weight once (4 + 4 + 4 bytes) and reads and writes the batch's
    float32 frontier once (4 + 4 bytes per node and source); top-k reads
    the scores once. The count is of the work, not of an
    implementation: the lax and the Pallas push are held to the same
    bytes. Operations (2 * batch * m a step) never bound it.
    """
    return l_max * (12 * m + 8 * batch * n) + 4 * batch * n
