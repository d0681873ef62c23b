"""The yardstick: chip peaks, and the bytes a kernel call of the data
path must move.

Counts come from the traffic's shapes, never from the program; a
model's FLOPs a sample are its reference's (``reference/<name>.py``).
"""
from __future__ import annotations

from typing import Dict

# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def decode_augment_bytes(batch: int, crop_hw, out_itemsize: int = 4,
                         scalars: int = 5) -> float:
    """Bytes one fused decode+augment call must move through HBM: it
    writes the (batch, crop_h, crop_w, 3) output in its own dtype and
    reads five int32 scalars per sample.  The pixels are synthesized
    from the scalars, so nothing else is read."""
    ch, cw = crop_hw
    return float(batch * (ch * cw * 3 * out_itemsize + scalars * 4))
