"""Peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s in bf16, 16 GB of HBM at 819 GB/s for one chip.  A kind that is
not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no peaks for device kind {device_kind!r}: add it to "
            f"benchmarks/peaks.py with its source") from None


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work`` (``flops`` and
    ``bytes``), and which of the two bounds it."""
    by_flops = work["flops"] / peak["flops_per_s"]
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
