"""The chip's published peaks, keyed by `jax.Device.device_kind`.

The benchmark's own copy (the program keeps one in
`telemetry/costbook.PEAKS`; a later PR may not move the yardstick by
editing that).  A device that is not in the table is an error, never a
default: a share of some other chip's peak is not a measurement."""

from __future__ import annotations

from typing import Any, Dict

PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "flops_per_s": 1.97e14,   # bf16, MXU
        "bytes_per_s": 8.19e11,   # HBM
        "hbm_bytes": 16 * 1024 ** 3,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM, 16 GB per chip",
    },
}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; the "
            f"table knows {sorted(PEAKS)}; add a row with its source")
    return PEAKS[device_kind]
