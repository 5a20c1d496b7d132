"""Published peaks of one chip, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s in bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM per chip.
A device that is not in the table is an error, never a default, and no
environment variable overrides a row.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks on record for device_kind {device_kind!r}; "
            "add a row to benchmark/peaks.py with its source"
        ) from None
