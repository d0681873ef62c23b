"""The yardstick: chip peaks, and the work a step or a kernel call must do.

Counts come from the configuration's shapes, never from the program.
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


def train_flops_per_sample(sizes: Dict) -> float:
    """Model FLOPs of one training sample: forward plus backward (twice
    the forward), recomputation not counted.

    Per token and layer the forward multiplies by every matmul parameter
    once (4 d^2 for q, k, v, o and 3 d f for the gated MLP, 2 FLOPs each)
    and attends to all T tokens (2 T d for the scores, 2 T d for the
    weighted sum; the encoder is not causal).  The head runs on token 0
    only (2 d C)."""
    L, d, f = sizes["n_layers"], sizes["d_model"], sizes["d_ff"]
    T, C = sizes["frontend_tokens"], sizes["n_classes"]
    per_token_layer = 2 * (4 * d * d + 3 * d * f) + 4 * T * d
    forward = T * L * per_token_layer + 2 * d * C
    return 3.0 * forward


def decode_augment_bytes(batch: int, crop_hw, out_itemsize: int = 4,
                         scalars: int = 5) -> float:
    """Bytes one fused decode+augment call must move through HBM: it
    writes the (batch, crop_h, crop_w, 3) output in its own dtype and
    reads five int32 scalars per sample.  The pixels are synthesized
    from the scalars, so nothing else is read."""
    ch, cw = crop_hw
    return float(batch * (ch * cw * 3 * out_itemsize + scalars * 4))
