"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page: 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
A device that is not in the table is an error, never a default: a roofline or MFU share against
an assumed peak is not a measurement.
"""

from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30,
        "ici_bits_per_s": 1600e9, "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks on record for device_kind {device_kind!r}; "
                       "add it to benchmark/peaks.py with its source")
    return PEAKS[device_kind]
