"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  A device
kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s of the MXU on bf16 operands
    int8_ops: float  # OP/s on int8 operands
    hbm_bytes: float  # bytes/s of HBM
    hbm_capacity: float  # bytes of HBM
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=819e9,
        hbm_capacity=16e9,
        source="Google Cloud documentation, TPU v5e system architecture"),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; known: "
            f"{sorted(PEAKS)}") from None
