"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bfloat16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float      # FLOP/s of one chip, bfloat16 matmul
    hbm_bytes_s: float     # HBM bandwidth of one chip, bytes/s
    hbm_bytes: float       # HBM capacity of one chip, bytes
    source: str


_V5E = Peaks(flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
             source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
