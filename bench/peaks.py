"""Peak rates of the chips this benchmark knows, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip does 197 TFLOP/s in bf16 and 393 TOP/s in int8, and holds 16 GB of HBM
at 819 GB/s. A device that is not in the table is an error: a share of
another chip's peak would mislabel every number.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16": 197e12,            # FLOP/s per chip
        "int8": 393e12,            # OP/s per chip
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no peaks known for device kind {device_kind!r}; known kinds: "
            f"{sorted(PEAKS)} ({SOURCE})") from None
