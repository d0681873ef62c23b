"""Device decode parity: the Pallas counter-hash decode kernel and the
fused decode+augment op against the host ``SyntheticDataset`` oracle.

The decode half must be *byte-identical* (uint8 out, integer hash all the
way).  The fused op must equal the decode-then-``augment_batch_seeded``
composition bitwise per sample — it runs the exact same float pipeline on
the same crop windows, just without materializing the decoded image.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.api  # noqa: F401  (break the pipeline<->api import cycle)
from repro.data.pipeline import fused_decode_seed as pipeline_fds
from repro.data.synthetic import DecodeHeavyDataset, SyntheticDataset
from repro.kernels.augment.ops import (augment_batch_seeded,
                                       decode_augment_batch_seeded)
from repro.kernels.decode.ops import (decode_batch, decode_batch_ref,
                                      decode_params, fused_decode_seed)

HW = (48, 40)
CROP = (32, 24)


def _ds(seed: int) -> SyntheticDataset:
    return SyntheticDataset("t", 256, 2048, image_hw=HW, crop_hw=CROP,
                            seed=seed)


# ------------------------------------------------------------- decode
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       sids=st.lists(st.integers(0, 255), min_size=1, max_size=5))
def test_decode_batch_matches_dataset(seed, sids):
    """Kernel decode is byte-identical to SyntheticDataset.decode for
    random (dataset seed, sample id, payload) triples."""
    ds = _ds(seed)
    payloads = [ds.encoded(s) for s in sids]
    out = decode_batch(payloads, sids, seed=seed, image_hw=HW)
    ref = np.stack([ds.decode(p, s) for p, s in zip(payloads, sids)])
    assert out.dtype == np.uint8
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("interpret", [True, None])
def test_decode_batch_interpret_paths(interpret):
    """Both the forced-interpret and auto-selected paths decode
    byte-identically (on CPU CI "auto" resolves to interpret via the
    cached module-level probe, but the contract must hold either way)."""
    ds = _ds(7)
    sids = [0, 3, 17, 101]
    payloads = [ds.encoded(s) for s in sids]
    out = decode_batch(payloads, sids, seed=7, image_hw=HW,
                       interpret=interpret)
    ref = np.stack([ds.decode(p, s) for p, s in zip(payloads, sids)])
    np.testing.assert_array_equal(out, ref)


def test_decode_params_match_dataset_derivation():
    ds = _ds(99)
    sids = [0, 1, 42, 200]
    payloads = [ds.encoded(s) for s in sids]
    bases, mixes = decode_params(99, sids, payloads)
    assert list(bases) == [ds.decode_base_seed(s) for s in sids]
    assert list(mixes) == [ds.decode_head_mix(p) for p in payloads]


def test_decode_jnp_oracle_agrees_with_kernel():
    ds = _ds(5)
    sids = [2, 9, 31]
    payloads = [ds.encoded(s) for s in sids]
    out = decode_batch(payloads, sids, seed=5, image_hw=HW)
    ref = np.asarray(decode_batch_ref(payloads, sids, seed=5, image_hw=HW))
    np.testing.assert_array_equal(out, ref)


# ------------------------------------------------------- fused op
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       sids=st.lists(st.integers(0, 255), min_size=1, max_size=4),
       epoch=st.integers(0, 3))
def test_fused_equals_decode_then_augment(seed, sids, epoch):
    """decode_augment_batch_seeded == decode + augment_batch_seeded,
    bitwise per sample, for random (seed, ids, epoch) draws."""
    ds = _ds(seed)
    payloads = [ds.encoded(s) for s in sids]
    aug_seeds = np.asarray([(epoch * 1_000_003 + s) & 0x7FFFFFFF
                            for s in sids], np.int64)
    fused = np.asarray(decode_augment_batch_seeded(
        payloads, sids, aug_seeds, ds_seed=seed, image_hw=HW,
        crop_h=CROP[0], crop_w=CROP[1]))
    imgs = np.stack([ds.decode(p, s) for p, s in zip(payloads, sids)])
    ref = augment_batch_seeded(imgs, aug_seeds, *CROP)
    np.testing.assert_array_equal(fused, ref)


def test_fused_bucket_padding_is_invisible():
    """Power-of-two padding (B=3 -> 4) and an exact bucket=B trace give
    the same rows — padding must never leak into the sliced output."""
    ds = _ds(11)
    sids = [5, 6, 7]
    payloads = [ds.encoded(s) for s in sids]
    seeds = np.asarray([s * 13 + 1 for s in sids], np.int64)
    kw = dict(ds_seed=11, image_hw=HW, crop_h=CROP[0], crop_w=CROP[1])
    padded = np.asarray(decode_augment_batch_seeded(
        payloads, sids, seeds, **kw))
    exact = np.asarray(decode_augment_batch_seeded(
        payloads, sids, seeds, bucket=len(sids), **kw))
    assert padded.shape[0] == len(sids)
    np.testing.assert_array_equal(padded, exact)


def test_fused_output_stays_on_device():
    import jax
    ds = _ds(1)
    out = decode_augment_batch_seeded(
        [ds.encoded(0)], [0], np.asarray([3], np.int64), ds_seed=1,
        image_hw=HW, crop_h=CROP[0], crop_w=CROP[1])
    assert isinstance(out, jax.Array)


# ------------------------------------------------- fused-decode gating
def test_fused_decode_seed_gating():
    base = _ds(42)
    assert fused_decode_seed(base) == 42
    heavy = DecodeHeavyDataset("h", 16, 1024, seed=42)
    assert fused_decode_seed(heavy) is None
    # the pipeline re-exports the same gate (lazy wrapper)
    assert pipeline_fds(base) == 42
    assert pipeline_fds(heavy) is None


# ------------------------------------------------ interpret-mode probe
def test_interpret_mode_only_on_cpu(monkeypatch):
    """Compiled Mosaic on TPU, the interpreter on CPU, and an error on
    any other backend — never a silent interpreter run there."""
    import jax

    from repro.kernels import device
    try:
        for backend, want in [("tpu", False), ("cpu", True)]:
            monkeypatch.setattr(jax, "default_backend", lambda: backend)
            device.default_interpret.cache_clear()
            assert device.default_interpret() is want
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        device.default_interpret.cache_clear()
        with pytest.raises(RuntimeError, match="'gpu'"):
            device.resolve_interpret(None)
        assert device.resolve_interpret(True) is True
    finally:
        monkeypatch.undo()
        device.default_interpret.cache_clear()
