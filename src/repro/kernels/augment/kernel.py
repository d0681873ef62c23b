"""Fused augmentation Pallas kernel (the paper's preprocessing hot-spot,
made TPU-native — DESIGN.md §7).

One grid step produces one row tile of one crop.  The uint8 source
image is staged in VMEM in the lane-dense view ``(H, W*3)``; the random
crop and the horizontal flip are two exact one-hot selections on the
MXU — a row selector ``(rows, H)`` on the left, a column selector
``(W*3, crop_w*3)`` that also mirrors pixel order under flip on the
right.  Pixel values 0..255 are exact in bf16 and every output sums a
single nonzero product, so the selection is bit-exact; it replaces a
dynamic slice at an arbitrary (unaligned) offset and a lane reversal,
which Mosaic does not lower.  Dequantize+normalize fuse into the store.
Output feeds the model in bf16 by default, so the host never touches
fp32 tensors (4x fewer bytes out — the op is memory-bound).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.device import resolve_interpret, row_block_iota, row_tile

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def normalize(pix, lane):
    """Per-channel normalize of the ``W*3`` lane-dense view: lane k holds
    channel k % 3.  Each channel's expression is the per-channel form's
    own (scalar constants — pallas kernels cannot capture array
    constants), selected per lane, so the float math is unchanged bit
    for bit."""
    c = lane % 3
    per_chan = [(pix / 255.0 - MEAN[ch]) / STD[ch] for ch in range(3)]
    return jnp.where(c == 0, per_chan[0],
                     jnp.where(c == 1, per_chan[1], per_chan[2]))


def _augment_kernel(top_ref, left_ref, flip_ref, img_ref, out_ref, *,
                    crop_w: int):
    b = pl.program_id(0)
    _, k = row_block_iota(out_ref)                     # (rows, crop_w*3)
    rows = out_ref.shape[1]
    H, W3 = img_ref.shape[1:]
    img = img_ref[0].astype(jnp.int32).astype(jnp.bfloat16)    # (H, W*3)
    # crop rows: sel_rows[r, s] = 1 where source row s = top + r
    out_row = jax.lax.broadcasted_iota(jnp.int32, (rows, H), 0) \
        + pl.program_id(1) * rows
    src_row = jax.lax.broadcasted_iota(jnp.int32, (rows, H), 1)
    sel_rows = (src_row == top_ref[b] + out_row).astype(jnp.bfloat16)
    picked = jnp.dot(sel_rows, img, preferred_element_type=jnp.float32)
    # crop + flip columns: output lane k = 3*j + c reads source lane
    # 3*left + k, or 3*left + (crop_w-1)*3 + 2*c - k under flip
    src_lane = jax.lax.broadcasted_iota(jnp.int32, (W3, k.shape[1]), 0)
    k_out = jax.lax.broadcasted_iota(jnp.int32, (W3, k.shape[1]), 1)
    c_out = k_out % 3
    want = left_ref[b] * 3 + jnp.where(
        flip_ref[b] != 0, (crop_w - 1) * 3 + 2 * c_out - k_out, k_out)
    sel_cols = (src_lane == want).astype(jnp.bfloat16)
    pix = jnp.dot(picked.astype(jnp.bfloat16), sel_cols,
                  preferred_element_type=jnp.float32)
    out_ref[0] = normalize(pix, k).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("crop_h", "crop_w",
                                             "out_dtype", "interpret"))
def augment(images: jax.Array, tops: jax.Array, lefts: jax.Array,
            flips: jax.Array, *, crop_h: int, crop_w: int,
            out_dtype=jnp.bfloat16,
            interpret: Optional[bool] = None) -> jax.Array:
    """images (B,H,W,3) uint8 -> (B,crop_h,crop_w,3) out_dtype.

    ``interpret=None`` (default) auto-selects via the cached module-level
    probe (repro.kernels.device): compiled Mosaic on TPU, interpreter on
    CPU.  The flag is static, so the choice is resolved once per (shape,
    dtype) trace.
    """
    interpret = resolve_interpret(interpret)
    B, H, W, C = images.shape
    assert C == 3
    rows = row_tile(crop_h, 8)
    kernel = functools.partial(_augment_kernel, crop_w=crop_w)
    out = pl.pallas_call(
        kernel,
        name="augment_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, crop_h // rows),
            in_specs=[pl.BlockSpec((1, H, W * 3),
                                   lambda b, t, *_: (b, 0, 0))],
            out_specs=pl.BlockSpec((1, rows, crop_w * 3),
                                   lambda b, t, *_: (b, t, 0))),
        out_shape=jax.ShapeDtypeStruct((B, crop_h, crop_w * 3), out_dtype),
        interpret=interpret,
    )(tops.astype(jnp.int32), lefts.astype(jnp.int32),
      flips.astype(jnp.int32), images.reshape(B, H, W * 3))
    return out.reshape(B, crop_h, crop_w, 3)
