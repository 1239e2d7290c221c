"""Published chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s. A kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float  # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float  # bytes of device memory


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
