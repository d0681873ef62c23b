"""Plain reference of the served images: what a row of a batch must hold.

Written from the data contract of the synthetic ImageNet-shaped dataset
and imports nothing of the program under test:

* a sample's encoded payload is ``n`` bytes drawn from
  ``default_rng(seed + sid)``, where ``n`` is the mean size times a
  lognormal(0, 0.35) factor from a fresh ``default_rng(seed + sid)``,
  clipped to [0.25, 4] and at least 1024;
* decoding yields an ``(h, w, 3)`` uint8 image whose flat pixel ``i`` is
  the splitmix32-style counter hash of ``(seed * 31 + sid) mod 2**32``
  at counter ``i``, plus the sum of the payload's first 4096 bytes,
  mod 256;
* augmenting draws ``top``, ``left``, ``flip`` from
  ``default_rng(aug_seed).integers`` in that order, crops, mirrors the
  columns when ``flip`` is 1, and normalizes ``(x / 255 - mean) / std``;
* the label is ``sid * 2654435761 mod n_classes``;
* an augmentation is seeded either by the epoch that produced it,
  ``(epoch * 1_000_003 + sid) mod 2**31``, or, for rows that background
  refills prepared, by ``sid ^ 0x5EED``.

The float math runs in float64, so the reference is the exact value and
the program's float32 rows are judged against it.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

import numpy as np

HASH_STEP = 0x9E3779B9
HASH_M1 = 0x7FEB352D
HASH_M2 = 0x846CA68B
MEAN = np.array([0.485, 0.456, 0.406], np.float64)
STD = np.array([0.229, 0.224, 0.225], np.float64)
REFILL_XOR = 0x5EED


def payload(seed: int, mean_bytes: int, sid: int) -> np.ndarray:
    rng = np.random.default_rng(seed + sid)
    scale = float(np.clip(rng.lognormal(mean=0.0, sigma=0.35), 0.25, 4.0))
    n = max(int(mean_bytes * scale), 1024)
    return np.random.default_rng(seed + sid).integers(
        0, 256, size=n, dtype=np.uint8)


def counter_hash(base: int, n: int) -> np.ndarray:
    x = (np.uint32(base & 0xFFFFFFFF)
         + np.arange(n, dtype=np.uint32) * np.uint32(HASH_STEP))
    x ^= x >> np.uint32(16)
    x *= np.uint32(HASH_M1)
    x ^= x >> np.uint32(15)
    x *= np.uint32(HASH_M2)
    x ^= x >> np.uint32(16)
    return (x & np.uint32(0xFF)).astype(np.int64)


def decoded(seed: int, mean_bytes: int, image_hw: Tuple[int, int],
            sid: int) -> np.ndarray:
    h, w = image_hw
    mix = int(payload(seed, mean_bytes, sid)[:4096].sum()) % 256
    pix = counter_hash((seed * 31 + sid) & 0xFFFFFFFF, h * w * 3)
    return ((pix + mix) % 256).reshape(h, w, 3)


def augmented(img: np.ndarray, crop_hw: Tuple[int, int],
              aug_seed: int) -> np.ndarray:
    h, w, _ = img.shape
    ch, cw = crop_hw
    rng = np.random.default_rng(aug_seed)
    top = int(rng.integers(0, h - ch + 1))
    left = int(rng.integers(0, w - cw + 1))
    flip = int(rng.integers(0, 2))
    crop = img[top:top + ch, left:left + cw]
    if flip:
        crop = crop[:, ::-1]
    return (crop.astype(np.float64) / 255.0 - MEAN) / STD


def label(sid: int, n_classes: int) -> int:
    return (sid * 2654435761) % n_classes


def aug_seeds(sid: int, epochs: Iterable[int]) -> List[int]:
    """Every seed a served augmentation of ``sid`` may carry: one per
    epoch that could have produced it, and the refill seed."""
    seeds = [(e * 1_000_003 + sid) & 0x7FFFFFFF for e in epochs]
    return seeds + [sid ^ REFILL_XOR]


def closest_row(row: np.ndarray, seed: int, mean_bytes: int,
                image_hw: Tuple[int, int], crop_hw: Tuple[int, int],
                sid: int, epochs: Iterable[int]
                ) -> Tuple[float, np.ndarray]:
    """(gap, reference row) for the valid augmentation of ``sid`` that
    lies closest to ``row``; the gap is the largest absolute difference
    over the row's elements."""
    img = decoded(seed, mean_bytes, image_hw, sid)
    best = (np.inf, None)
    for s in aug_seeds(sid, epochs):
        ref = augmented(img, crop_hw, s)
        gap = float(np.max(np.abs(row.astype(np.float64) - ref)))
        if gap < best[0]:
            best = (gap, ref)
    return best
