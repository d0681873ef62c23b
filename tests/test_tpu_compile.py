"""The device-route Pallas kernels compile for a TPU v5e.

No chip is needed: the TPU compiler is installed with JAX and compiles
for a described (not attached) ``v5e:2x2`` topology.  This catches what
interpret mode cannot — blocks the Mosaic lowering refuses, unaligned
dynamic slices, VMEM overruns — at the ImageNet shape the device route
serves (256x256 decode, 224x224 crop, batch 32) and at the ``tiny``
dataset's shape the CPU tests use.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library at a time, and every test worker
imports this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.augment.kernel import augment
from repro.kernels.decode.kernel import decode, decode_augment

B = 32
# (image side, crop side): the ImageNet shape and the tiny dataset's
SIZES = [(256, 224), (64, 56)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_tpu_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("img,crop", SIZES)
def test_decode_compiles_for_v5e(one_chip, img, crop):
    u32 = _spec((B,), jnp.uint32, one_chip)
    i32 = _spec((B,), jnp.int32, one_chip)
    _assert_tpu_kernel(decode.lower(u32, i32, h=img, w=img,
                                    interpret=False))


@pytest.mark.parametrize("img,crop", SIZES)
def test_decode_augment_compiles_for_v5e(one_chip, img, crop):
    u32 = _spec((B,), jnp.uint32, one_chip)
    i32 = _spec((B,), jnp.int32, one_chip)
    _assert_tpu_kernel(decode_augment.lower(
        u32, i32, i32, i32, i32, img_h=img, img_w=img, crop_h=crop,
        crop_w=crop, out_dtype=jnp.float32, interpret=False))


@pytest.mark.parametrize("img,crop", SIZES)
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_augment_compiles_for_v5e(one_chip, img, crop, out_dtype):
    images = _spec((B, img, img, 3), jnp.uint8, one_chip)
    i32 = _spec((B,), jnp.int32, one_chip)
    _assert_tpu_kernel(augment.lower(images, i32, i32, i32, crop_h=crop,
                                     crop_w=crop, out_dtype=out_dtype,
                                     interpret=False))
