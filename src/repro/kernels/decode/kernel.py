"""Pallas decode + fused decode/augment kernels.

One grid step synthesizes one row tile of one image: the counter hash
runs over an index block built from ``broadcasted_iota``, so there is no
source tile to stage — the "decode" reads nothing but two scalars per
sample (base seed + header mix), delivered through scalar prefetch.
The fused variant hashes *only the crop window's* source indices
(mirrored columns under flip) and feeds the exact float pipeline of the
augment kernel, emitting the normalized crop with no intermediate
decoded image anywhere.

Both kernels work on the lane-dense view ``(B, rows, width*3)``: the
3-channel axis is folded into the minor (lane) dimension, where it
would otherwise pad to 128 lanes on a TPU.  The ``(B, h, w, 3)``
reshape happens outside the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.augment.kernel import normalize
from repro.kernels.decode.ref import pixel_hash_jnp
from repro.kernels.device import resolve_interpret, row_block_iota, row_tile


def _decode_kernel(base_ref, mix_ref, out_ref):
    b = pl.program_id(0)
    row, lane = row_block_iota(out_ref)
    idx = (row * out_ref.shape[2] + lane).astype(jnp.uint32)
    u8 = pixel_hash_jnp(base_ref[b], idx).astype(jnp.int32)
    out_ref[0] = ((u8 + mix_ref[b]) % 256).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("h", "w", "interpret"))
def decode(bases: jax.Array, mixes: jax.Array, *, h: int, w: int,
           interpret: Optional[bool] = None) -> jax.Array:
    """(B,) uint32 base seeds + (B,) int32 header mixes -> (B,h,w,3) uint8.

    Byte-identical to ``SyntheticDataset.decode`` per sample (pinned by
    tests/test_decode_kernel.py).
    """
    interpret = resolve_interpret(interpret)
    B = bases.shape[0]
    rows = row_tile(h, 32)
    out = pl.pallas_call(
        _decode_kernel,
        name="decode_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, h // rows), in_specs=[],
            out_specs=pl.BlockSpec((1, rows, w * 3),
                                   lambda b, t, *_: (b, t, 0))),
        out_shape=jax.ShapeDtypeStruct((B, h, w * 3), jnp.uint8),
        interpret=interpret,
    )(bases.astype(jnp.uint32), mixes.astype(jnp.int32))
    return out.reshape(B, h, w, 3)


def _decode_augment_kernel(base_ref, mix_ref, top_ref, left_ref, flip_ref,
                           out_ref, *, img_w: int, crop_w: int):
    b = pl.program_id(0)
    i, k = row_block_iota(out_ref)
    c = k % 3
    # the flip is a source-index mirror: output lane k = 3*j + c reads
    # source column crop_w-1-j, i.e. lane (crop_w-1)*3 + 2*c - k of the
    # crop window — hash the pixel the flipped crop would have read,
    # instead of materializing then reversing
    src = jnp.where(flip_ref[b] != 0, (crop_w - 1) * 3 + 2 * c - k, k)
    idx = ((top_ref[b] + i) * (img_w * 3) + left_ref[b] * 3
           + src).astype(jnp.uint32)
    u8 = pixel_hash_jnp(base_ref[b], idx).astype(jnp.int32)
    pix = (u8 + mix_ref[b]) % 256
    # from here: the augment kernel's exact float pipeline (/255, scalar
    # per-channel normalize) so fused == decode-then-augment bitwise
    out_ref[0] = normalize(pix.astype(jnp.float32), k).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("img_h", "img_w", "crop_h",
                                             "crop_w", "out_dtype",
                                             "interpret"))
def decode_augment(bases: jax.Array, mixes: jax.Array, tops: jax.Array,
                   lefts: jax.Array, flips: jax.Array, *, img_h: int,
                   img_w: int, crop_h: int, crop_w: int,
                   out_dtype=jnp.float32,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Fused decode+crop+flip+normalize: per-sample scalars in, augmented
    (B,crop_h,crop_w,3) out — one kernel, one device round-trip."""
    interpret = resolve_interpret(interpret)
    del img_h  # part of the contract/signature; only img_w indexes memory
    B = bases.shape[0]
    rows = row_tile(crop_h, 8)
    kernel = functools.partial(_decode_augment_kernel, img_w=img_w,
                               crop_w=crop_w)
    out = pl.pallas_call(
        kernel,
        name="decode_augment_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B, crop_h // rows), in_specs=[],
            out_specs=pl.BlockSpec((1, rows, crop_w * 3),
                                   lambda b, t, *_: (b, t, 0))),
        out_shape=jax.ShapeDtypeStruct((B, crop_h, crop_w * 3), out_dtype),
        interpret=interpret,
    )(bases.astype(jnp.uint32), mixes.astype(jnp.int32),
      tops.astype(jnp.int32), lefts.astype(jnp.int32),
      flips.astype(jnp.int32))
    return out.reshape(B, crop_h, crop_w, 3)
