"""The counts the benchmark's numbers rest on, against hand counts: the
yardstick's peaks and kernel bytes, and the FLOPs a sample of each
configuration's reference."""
import json
import os

import benchpath  # noqa: F401
import pytest

from benchlib import yardstick
from benchlib.catalog import Catalog


def _config(name):
    with open(os.path.join(benchpath.BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def train_flops_per_sample(name):
    """The FLOPs a sample of the reference the configuration names."""
    config = _config(name)
    return Catalog().reference(config).train_flops_per_sample(
        config["sizes"])


@pytest.mark.parametrize("name, per_sample", [
    # T * L * (2 (4 d^2 + 3 d f) + 4 T d) + 2 d C, times 3:
    # 197 * 12 * (2 (4*768^2 + 3*768*3072) + 4*197*768) + 2*768*1000
    ("vit-base-16", 3 * 46_051_196_928),
    # 197 * 32 * (2 (4*1280^2 + 3*1280*5120) + 4*197*1280) + 2*1280*1000
    ("vit-huge", 3 * 336_872_181_760),
])
def test_train_flops_per_sample_matches_hand_count(name, per_sample):
    assert train_flops_per_sample(name) == per_sample


def test_flops_are_about_the_published_ratio():
    # ~138 GFLOP for ViT-B/16 and ~1.01 TFLOP for ViT-H a sample
    assert round(train_flops_per_sample("vit-base-16") / 1e9) == 138
    assert round(train_flops_per_sample("vit-huge") / 1e10) == 101


def test_decode_augment_bytes():
    # a 128-sample call writes 128 x 224 x 224 x 3 float32 and reads five
    # int32 scalars a sample
    assert yardstick.decode_augment_bytes(128, (224, 224)) == \
        128 * 224 * 224 * 3 * 4 + 128 * 5 * 4


def test_unknown_device_is_an_error():
    assert yardstick.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")
