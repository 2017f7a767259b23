"""Chip peaks keyed by ``device_kind``, and the least bytes each codec
geometry must move through HBM.

Peaks: Google Cloud documentation, "TPU v5e" (per chip).  A device kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}") from None


def codec_min_bytes(data_bytes: int, k: int, n: int) -> int:
    """Least HBM traffic of one encode: read the k data chunks once and
    write the n - k coded chunks once, so data x n / k.  It depends on
    the geometry alone, not on the kernels that compute it."""
    if data_bytes % k:
        raise ValueError(f"{data_bytes} data bytes do not split into {k}")
    return data_bytes * n // k
