"""DSI pipeline routes + prefetch-loop bugfixes.

The prefetch queue neither drops nor duplicates batches under a slow
consumer, prefetch/refill failures are recorded instead of swallowed,
cache-hit fetch time is accounted as the lookup interval, the NumPy and
Pallas batch augments agree within float tolerance with per-sample seed
determinism, both routes (``"per-sample"``, ``"device"``) cover an epoch
in sampling order through ``start_prefetch``/``get``, and the removed
stage-parallel executor and augment-backend option are refused.
"""
import dataclasses
import time

import numpy as np
import pytest

from repro.api import (AZURE_NC96, SenecaConfig, SenecaServer,
                       SessionClosed, TelemetryAggregator,
                       TelemetrySnapshot)
from repro.data.augment import augment_batch_np
from repro.data.pipeline import EXECUTORS, DSIPipeline
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny
from repro.kernels.augment.ops import augment_batch_seeded

# the two batch augments, called alike: (images, crop_hw, seeds)
AUGMENTERS = {
    "augment_batch_np": augment_batch_np,
    "augment_batch_seeded":
        lambda imgs, crop_hw, seeds: augment_batch_seeded(imgs, seeds,
                                                          *crop_hw),
}


def _server(ds, **kw):
    kw.setdefault("cache_frac", 0.4)
    return SenecaServer.for_dataset(ds, hardware=AZURE_NC96, seed=1, **kw)


# ----------------------------------------------------------------------
# satellite bugfix: prefetch holds the built batch under a slow consumer
def test_prefetch_slow_consumer_no_drop_no_dup():
    ds = tiny(n=120)
    server = _server(ds, use_ods=False)          # naive: exact epoch cover
    pipe = DSIPipeline(server.open_session(batch_size=20), RemoteStorage(ds),
                       n_workers=2, prefetch=1)
    pipe.start_prefetch()
    seen = []
    for _ in range(120 // 20):
        time.sleep(0.05)                         # slower than production
        seen.extend(pipe.get(timeout=30.0)["ids"].tolist())
    # the seed dropped every batch built while the queue was full, so a
    # slow consumer skipped sample ids; held-and-reoffered batches cover
    # the first epoch exactly, in order, no gaps and no duplicates
    assert sorted(seen) == list(range(120)), \
        "prefetch dropped or duplicated batches under a slow consumer"
    pipe.stop()
    server.close()


def test_prefetch_records_next_batch_exception():
    ds = tiny(n=64)
    server = _server(ds)
    pipe = DSIPipeline(server.open_session(batch_size=8), RemoteStorage(ds),
                       n_workers=2, prefetch=2)

    def boom():
        raise RuntimeError("synthetic next_batch failure")
    pipe.next_batch = boom
    pipe.start_prefetch()
    with pytest.raises(RuntimeError, match="prefetch thread died"):
        pipe.get(timeout=10.0)
    assert server.stats()["telemetry"]["errors"]["prefetch"] == 1
    pipe.stop()
    server.close()


# ----------------------------------------------------------------------
# satellite bugfix: cache-hit fetch time is the lookup interval
def test_hit_fetch_time_accounts_lookup_interval():
    ds = tiny(n=32)
    server = _server(ds, split=(0.0, 0.0, 1.0))
    sess = server.open_session(batch_size=4)
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=1)
    out = np.zeros((*ds.crop_hw, 3), np.float32)
    assert sess.admit(3, "augmented", out, out.nbytes)

    # the pipeline's serving seam is lookup_tiered (it also names the
    # tier that answered, for per-tier bandwidth telemetry)
    orig = pipe.session.lookup_tiered

    def slow_lookup(sid):
        time.sleep(0.02)
        return orig(sid)
    pipe.session.lookup_tiered = slow_lookup
    got = pipe._produce_sample(3, epoch_tag=0)
    assert got is out or np.array_equal(got, out)
    # the seed charged ~0 here (timer started after the lookup returned)
    assert pipe.times.fetch >= 0.015, pipe.times.fetch
    pipe.session.lookup_tiered = orig
    pipe.stop()
    server.close()


# ----------------------------------------------------------------------
# satellite bugfix: refill failures are counted, not swallowed
def test_refill_errors_surface_in_stats():
    ds = tiny(n=32)
    server = _server(ds)
    pipe = DSIPipeline(server.open_session(batch_size=4), RemoteStorage(ds),
                       n_workers=1)

    def bad_fetch(sid):
        raise IOError("storage down")
    pipe.storage.fetch = bad_fetch
    pipe._refill_one(5)
    pipe._refill_one(6)
    st = server.stats()
    assert st["refill_errors"] == 2
    assert st["telemetry"]["errors"]["refill"] == 2
    pipe.stop()
    server.close()


# ----------------------------------------------------------------------
# batch augments: parity + per-sample seed determinism
@pytest.mark.parametrize("augmenter", sorted(AUGMENTERS))
def test_batch_augment_seed_determinism(augmenter):
    """Same seed -> same output row, independent of batch composition
    (a route's augment groups vary with cache hits)."""
    aug = AUGMENTERS[augmenter]
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, size=(7, 48, 40, 3), dtype=np.uint8)
    seeds = (np.arange(7) * 977 + 13).astype(np.int64)
    full = aug(imgs, (32, 24), seeds)
    assert full.shape == (7, 32, 24, 3) and full.dtype == np.float32
    for i in (0, 3, 6):                 # singleton batches (bucket B=1)
        solo = aug(imgs[i:i + 1], (32, 24), seeds[i:i + 1])
        np.testing.assert_allclose(solo[0], full[i], atol=2e-6)


def test_batch_augment_parity_numpy_vs_pallas():
    rng = np.random.default_rng(7)
    imgs = rng.integers(0, 256, size=(6, 64, 64, 3), dtype=np.uint8)
    seeds = (np.arange(6) * 1_000_003 + 42).astype(np.int64)
    out_np = augment_batch_np(imgs, (56, 56), seeds)
    out_pl = augment_batch_seeded(imgs, seeds, 56, 56)
    np.testing.assert_allclose(out_pl, out_np, atol=2e-6)


# ----------------------------------------------------------------------
# both routes behind start_prefetch()/get()
@pytest.mark.parametrize("executor", EXECUTORS)
def test_prefetch_epoch_coverage_in_order(executor):
    """Every id of the epoch exactly once, in the sampler's order."""
    ds = tiny(n=96)
    ref = _server(ds, use_ods=False)             # naive: exact epoch cover
    with ref.open_session(batch_size=12) as sess:
        order = [int(s) for _ in range(96 // 12)
                 for s in sess.next_batch_ids()[0]]
    ref.close()
    assert sorted(order) == list(range(96))
    server = _server(ds, use_ods=False)
    pipe = DSIPipeline(server.open_session(batch_size=12), RemoteStorage(ds),
                       n_workers=4, executor=executor)
    pipe.start_prefetch()
    seen = []
    for _ in range(96 // 12):
        b = pipe.get(timeout=60.0)
        assert b["images"].shape == (12, *ds.crop_hw, 3)
        assert b["labels"].shape == (12,)
        assert np.isfinite(np.asarray(b["images"])).all()
        assert abs(float(np.asarray(b["images"]).mean())) < 2.0
        seen.extend(b["ids"].tolist())
    assert seen == order, f"{executor} route dropped/duplicated/reordered"
    pipe.stop()
    server.close()


def test_host_prefetch_get_matches_serial_next_batch():
    """The host route's prefetched batches equal its serial ones, bitwise:
    seeds are per sample, not per thread or batch.

    The augmented tier is disabled (encoded-only split): background
    refills admit entries under their own seed, and whether a sample is
    served from a refill is a thread race — with no augmented tier every
    sample is augmented fresh from its (epoch, sid) seed.
    """
    def run(prefetch):
        ds = tiny(n=48)
        server = _server(ds, use_ods=False, split=(1.0, 0.0, 0.0))
        pipe = DSIPipeline(server.open_session(batch_size=8),
                           RemoteStorage(ds), n_workers=3)
        if prefetch:
            pipe.start_prefetch()
        out = {}
        for _ in range(48 // 8):
            b = pipe.get(timeout=60.0) if prefetch else pipe.next_batch()
            for i, sid in enumerate(b["ids"].tolist()):
                out[sid] = b["images"][i]
        pipe.stop()
        server.close()
        return out

    a, b = run(False), run(True)
    assert a.keys() == b.keys()
    for sid in a:
        np.testing.assert_array_equal(a[sid], b[sid])


@pytest.mark.parametrize("executor", EXECUTORS)
def test_session_close_fails_fast(executor):
    """Closing the session externally must surface as SessionClosed from
    the next batch, on either route, not as a hang."""
    ds = tiny(n=64)
    server = _server(ds)
    sess = server.open_session(batch_size=8)
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                       executor=executor)
    pipe.next_batch()
    sess.close()
    t0 = time.monotonic()
    with pytest.raises(SessionClosed):
        pipe.next_batch()
    assert time.monotonic() - t0 < 5.0
    pipe.stop()
    server.close()


def test_calibration_rates_scale_by_concurrency():
    """t_a/t_da are node-aggregate rates: per-sample latencies scaled by
    the registered worker concurrency."""
    tel = TelemetryAggregator()
    tel.add_concurrency(4)
    tel.record_stage("decode", 0.010)
    tel.record_stage("augment", 0.020)
    snap = tel.snapshot()
    assert snap.t_a == pytest.approx(4 / 0.020)
    assert snap.t_da == pytest.approx(4 / 0.030)


def test_unknown_executor_rejected():
    ds = tiny(n=16)
    server = _server(ds)
    with pytest.raises(ValueError, match="unknown executor"):
        DSIPipeline(server.open_session(batch_size=4), RemoteStorage(ds),
                    executor="warp-speed")
    # legacy call style: validation must fire BEFORE the job registers,
    # or the failed constructor leaks a phantom job into the shared
    # service (inflating the refcount-eviction threshold)
    with pytest.raises(ValueError, match="unknown executor"):
        DSIPipeline(7, server.service, RemoteStorage(ds), 4,
                    executor="warp-speed")
    assert 7 not in server.service._samplers
    server.close()


# ----------------------------------------------------------------------
# the removed stage-parallel executor and augment-backend option
def test_executors_are_the_two_routes():
    assert EXECUTORS == ("per-sample", "device")


def test_stage_parallel_executor_rejected():
    ds = tiny(n=16)
    server = _server(ds)
    with pytest.raises(ValueError, match="unknown executor"):
        DSIPipeline(server.open_session(batch_size=4), RemoteStorage(ds),
                    executor="stage-parallel")
    server.close()


@pytest.mark.parametrize("where", ["pipeline", "server", "config"])
def test_augment_backend_option_rejected(where):
    ds = tiny(n=16)
    if where == "config":
        assert "augment_backend" not in {
            f.name for f in dataclasses.fields(SenecaConfig)}
        with pytest.raises(TypeError):
            _server(ds, augment_backend="pallas")
        return
    server = _server(ds)
    with pytest.raises(TypeError):
        if where == "pipeline":
            DSIPipeline(server.open_session(batch_size=4),
                        RemoteStorage(ds), augment_backend="pallas")
        else:
            SenecaServer(server.service.cfg, augment_backend="pallas")
    server.close()


def test_telemetry_has_no_queue_or_worker_group_fields():
    """The snapshot and stats() carry no queue gauges and no per-stage
    worker groups; t_da is conc/(dec+aug) whatever the reporter."""
    names = {f.name for f in dataclasses.fields(TelemetrySnapshot)}
    assert not names & {"queue_depth", "queue_occupancy"}
    tel = TelemetryAggregator()
    assert not hasattr(tel, "record_queue")
    with pytest.raises(TypeError):
        tel.record_stage("decode", 0.010, workers=2)
    tel.add_concurrency(3)
    tel.record_stage("decode", 0.004)
    tel.record_stage("augment", 0.002)
    snap = tel.snapshot()
    assert snap.t_da == pytest.approx(3 / (0.004 + 0.002))
    assert not {"queue_depth", "queue_occupancy"} & set(tel.as_dict())


def test_stats_have_no_augment_backend_key():
    ds = tiny(n=32)
    server = _server(ds)
    pipe = DSIPipeline(server.open_session(batch_size=8), RemoteStorage(ds),
                       n_workers=1)
    pipe.next_batch()
    stats = server.stats()
    assert "augment_backend" not in stats
    assert not {"queue_depth", "queue_occupancy"} & set(stats["telemetry"])
    pipe.stop()
    server.close()


# ----------------------------------------------------------------------
# device executor semantics (fused Pallas decode+augment + HBM tier)
def test_device_executor_epoch_coverage_and_bitwise_parity():
    """One epoch through the device route, augmented/decoded tiers
    disabled so every sample takes the fused kernel fresh: batch rows
    must equal decode + Pallas augment_batch_seeded *bitwise* (the
    kernel parity contract, here exercised through the live stack)."""
    from repro.data.pipeline import _aug_seed
    from repro.kernels.augment.ops import augment_batch_seeded
    ds = tiny(n=64)
    server = _server(ds, use_ods=False, split=(1.0, 0.0, 0.0))
    sess = server.open_session(batch_size=8)
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                       executor="device")
    seen = []
    for _ in range(64 // 8):
        epoch = sess.epoch
        b = pipe.next_batch()
        assert b["images"].shape == (8, *ds.crop_hw, 3)
        ids = b["ids"].tolist()
        seen.extend(ids)
        imgs = np.stack([ds.decode(ds.encoded(s), s) for s in ids])
        seeds = np.asarray([_aug_seed(epoch, s) for s in ids], np.int64)
        ref = augment_batch_seeded(imgs, seeds, *ds.crop_hw)
        np.testing.assert_array_equal(np.asarray(b["images"]), ref)
    assert sorted(seen) == list(range(64)), \
        "device executor dropped/duplicated samples"
    pipe.stop()
    server.close()


def test_device_executor_hbm_hits_are_zero_h2d():
    """With an HBM tier large enough for every augmented sample, the
    second epoch serves device-resident rows: no bytes cross the h2d
    channel and the HBM tier reports hits."""
    ds = tiny(n=64)
    hbm = int(1.2 * 64 * ds.augmented_bytes())
    server = _server(ds, use_ods=False, split=(0.5, 0.0, 0.5),
                     device_cache_bytes=hbm, hbm_split=(0.0, 0.0, 1.0))
    sess = server.open_session(batch_size=8)
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                       executor="device")
    tel = server.service.telemetry
    for _ in range(64 // 8):                      # epoch 1: all fresh
        pipe.next_batch()
    h2d_after_e1 = tel.channel_total_bytes("h2d")
    for _ in range(64 // 8):                      # epoch 2: all HBM hits
        b = pipe.next_batch()
        assert b["images"].shape == (8, *ds.crop_hw, 3)
    assert tel.channel_total_bytes("h2d") == h2d_after_e1, \
        "HBM-hit epoch shipped host->device payload bytes"
    stats = server.stats()
    assert stats["residency_counts"]["hbm"] == 64
    assert stats["hbm"]["augmented"]["hbm_hits"] > 0
    pipe.stop()
    server.close()


def test_device_executor_rejects_non_fusable_dataset():
    from repro.data.synthetic import DecodeHeavyDataset
    ds = DecodeHeavyDataset("h", 32, 1024)
    server = _server(ds, use_ods=False)
    with pytest.raises(ValueError, match="device executor"):
        DSIPipeline(server.open_session(batch_size=8), RemoteStorage(ds),
                    executor="device")
    server.close()


def test_device_executor_decoded_hbm_hit_stays_on_device():
    """A decoded-form value served from the HBM tier (hbm_split with
    z_d > 0) must be augmented on device: no d2h download metered on
    the cache channel, no re-upload on h2d, and the rows still match
    the host decode+augment reference bitwise."""
    from repro.data.pipeline import _aug_seed
    from repro.kernels.augment.ops import augment_batch_seeded
    ds = tiny(n=32)
    hbm = int(1.2 * 32 * ds.decoded_bytes())
    server = _server(ds, use_ods=False, split=(0.0, 1.0, 0.0),
                     device_cache_bytes=hbm, hbm_split=(0.0, 1.0, 0.0))
    sess = server.open_session(batch_size=8)
    # pre-warm every sample's decoded form; array payloads the HBM tier
    # admits go device-resident immediately
    for sid in range(32):
        img = ds.decode(ds.encoded(sid), sid)
        assert sess.admit(sid, "decoded", img, img.nbytes)
    assert server.stats()["hbm"]["decoded"]["hbm_entries"] == 32
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                      executor="device")
    tel = server.service.telemetry
    seen = []
    for _ in range(32 // 8):
        epoch = sess.epoch
        b = pipe.next_batch()
        ids = b["ids"].tolist()
        seen.extend(ids)
        imgs = np.stack([ds.decode(ds.encoded(s), s) for s in ids])
        seeds = np.asarray([_aug_seed(epoch, s) for s in ids], np.int64)
        ref = augment_batch_seeded(imgs, seeds, *ds.crop_hw)
        np.testing.assert_array_equal(np.asarray(b["images"]), ref)
    assert sorted(seen) == list(range(32))
    assert tel.channel_total_bytes("cache") == 0, \
        "decoded HBM hit metered a device->host download as cache bytes"
    assert tel.channel_total_bytes("h2d") == 0, \
        "decoded HBM hit re-uploaded device-resident pixels"
    pipe.stop()
    server.close()
