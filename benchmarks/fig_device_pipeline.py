"""Device-resident preprocessing: fused decode+augment + HBM tier.

The device route removes the host from the steady-state data path twice
over: cold samples run decode and augment fused in one Pallas launch fed
by per-sample scalars (no decoded image, no payload upload), and warm
samples are served straight out of the device-side HBM cache tier with
zero host→device bytes.  This script drives both on the *live* stack:

* ``fused-device`` — the device executor under a constrained storage
  token bucket, with a small host DRAM budget plus a device cache tier
  sized for the augmented working set (``capacity``/``lru`` policies, so
  augmented rows persist across epochs of the single job).  It warms one
  full epoch (jit traces + cache fill) and then reports the median
  samples/s of three timed windows.  That rate is a CPU timing and
  gates nothing; speed is judged on the chip (``bench/``).
* ``zero_h2d_epoch`` — a small all-resident configuration runs two
  epochs and records the ``"h2d"`` telemetry channel around epoch 2.
  The zero-copy claim is an exact byte count, not a rate.

Emits ``BENCH_device.json``; ``--check`` asserts that the all-HBM-hit
epoch moved zero h2d payload bytes (the CI smoke gate).
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, List, Tuple

from benchmarks.common import write_bench_json
from repro.api import SenecaServer
from repro.data.pipeline import DSIPipeline
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny


def run_mode(*, n_samples: int, batch: int, windows: int,
             window_batches: int, bandwidth: float, n_workers: int,
             cache_frac: float, seed: int = 0) -> Dict:
    ds = tiny(n=n_samples)
    hbm = int(1.2 * n_samples * ds.augmented_bytes())
    server = SenecaServer.for_dataset(
        ds, cache_bytes=int(cache_frac * n_samples * ds.augmented_bytes()),
        seed=seed, use_ods=False, admission="capacity", eviction="lru",
        device_cache_bytes=hbm, hbm_split=(0.0, 0.0, 1.0))
    storage = RemoteStorage(ds, bandwidth=bandwidth)
    pipe = DSIPipeline(server.open_session(batch_size=batch), storage,
                       n_workers=n_workers, seed=seed, executor="device")
    for _ in range(n_samples // batch):   # one warm epoch: traces + fill
        pipe.next_batch()
    rates = []
    for _ in range(windows):
        t0 = time.monotonic()
        for _ in range(window_batches):
            pipe.next_batch()
        rates.append(window_batches * batch / (time.monotonic() - t0))
    stats = server.stats()
    result = {
        "mode": "fused-device",
        "samples_per_s": statistics.median(rates),
        "window_samples_per_s": [round(r, 1) for r in rates],
        "stage_times_s": pipe.times.as_dict(),
        "cache_hit_rate": stats["cache_lookup_hit_rate"],
        "h2d_bytes": server.service.telemetry.channel_total_bytes("h2d"),
        "storage_fetches": storage.fetches,
        "residency_counts": stats["residency_counts"],
        "hbm_hits": sum(s["hbm_hits"] for s in stats["hbm"].values()),
        "hbm_bytes_used": stats["hbm_bytes_used"],
    }
    pipe.stop()
    server.close()
    return result


def run_zero_h2d_epoch(*, n_samples: int, batch: int, seed: int = 0) -> Dict:
    """Two epochs with an HBM tier sized for the whole augmented set:
    epoch 2 must serve every sample device-resident with zero bytes on
    the h2d channel."""
    ds = tiny(n=n_samples)
    hbm = int(1.2 * n_samples * ds.augmented_bytes())
    server = SenecaServer.for_dataset(
        ds, cache_frac=0.25, seed=seed, use_ods=False,
        admission="capacity", eviction="lru",
        device_cache_bytes=hbm, hbm_split=(0.0, 0.0, 1.0))
    pipe = DSIPipeline(server.open_session(batch_size=batch),
                       RemoteStorage(ds), n_workers=2, executor="device",
                       seed=seed)
    tel = server.service.telemetry
    for _ in range(n_samples // batch):           # epoch 1: fill HBM
        pipe.next_batch()
    before = tel.channel_total_bytes("h2d")
    for _ in range(n_samples // batch):           # epoch 2: all HBM hits
        pipe.next_batch()
    stats = server.stats()
    result = {
        "epoch1_h2d_bytes": before,
        "epoch2_h2d_bytes": tel.channel_total_bytes("h2d") - before,
        "residency_counts": stats["residency_counts"],
        "hbm_hits": sum(s["hbm_hits"] for s in stats["hbm"].values()),
    }
    pipe.stop()
    server.close()
    return result


def run(full: bool = False, check: bool = False) -> List[Tuple[str, str]]:
    knobs = dict(n_samples=4_096 if full else 1_024, batch=16,
                 windows=3, window_batches=16 if full else 8,
                 bandwidth=8e6, n_workers=4, cache_frac=0.15)
    dev = run_mode(**knobs)
    zero = run_zero_h2d_epoch(n_samples=512 if full else 128, batch=16)
    payload = {"config": {k: str(v) for k, v in knobs.items()},
               "zero_h2d_epoch": zero, "fused-device": dev}
    path = write_bench_json("device", payload)
    rows = [("fig_device/fused-device",
             f"sps={dev['samples_per_s']:.0f} h2d={dev['h2d_bytes']} "
             f"windows={dev['window_samples_per_s']} json={path}"),
            ("fig_device/zero_h2d_epoch",
             f"epoch2_h2d={zero['epoch2_h2d_bytes']} "
             f"hbm_hits={zero['hbm_hits']} "
             f"hbm_resident={zero['residency_counts'].get('hbm', 0)}")]
    if check:
        assert zero["epoch2_h2d_bytes"] == 0, (
            f"all-HBM-hit epoch shipped {zero['epoch2_h2d_bytes']} h2d "
            f"bytes (expected 0)")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="assert the HBM-hit epoch is zero-h2d (CI)")
    args = ap.parse_args()
    for name, derived in run(full=args.full, check=args.check):
        print(f"{name},{derived}")
