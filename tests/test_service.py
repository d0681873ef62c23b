"""Sharded data plane: router, shards, transports, server integration.

Tier-1 coverage for ``repro.service`` (the hypothesis sweeps live in
``test_service_properties.py``, CI stress job):

* consistent-hash router — scalar/vector agreement, grouping, balance;
* CacheShard protocol handling (errors stay Responses, never raises);
* ShardedCache over the sim transport — the full TieredCache surface,
  eviction piggybacking, residency merges, per-shard spill dirs;
* the determinism acceptance gate — one 2-job VirtualClock trace run on
  ``shards=1`` (classic engine) and ``shards=2`` (sim transport)
  produces identical per-job sample-id sequences, and two fresh
  ``shards=2`` runs are byte-identical;
* process transport — spawn handshake, zero-copy payload parity,
  shard-side produce parity, idempotent close, failed-start cleanup.
"""
import glob
import os
import tempfile

import numpy as np
import pytest

from repro.api import (JobSpec, SenecaServer, ShardedCache, ShardRouter,
                       VirtualClock, WorkloadRunner)
from repro.cache.store import FORMS, TieredCache
from repro.data.augment import augment_np
from repro.data.pipeline import DSIPipeline
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny
from repro.service import CacheShard, Request, Response, ShardConfig
from repro.service.router import _splitmix64_np, splitmix64
from repro.service.shard import produce_seed
from repro.workload.runner import deterministic_runner

SPLIT = (0.2, 0.4, 0.4)


# ----------------------------------------------------------------------
# router
def test_router_scalar_vector_agree():
    r = ShardRouter(5, vnodes=32, seed=3)
    keys = np.arange(512, dtype=np.int64)
    vec = r.shard_of_many(keys)
    assert [r.shard_of(int(k)) for k in keys] == list(vec)
    assert splitmix64(12345) == int(_splitmix64_np(
        np.asarray([12345], np.uint64))[0])


def test_router_group_partitions_exactly():
    r = ShardRouter(4, seed=1)
    keys = list(range(300))
    groups = r.group(keys)
    seen = sorted(int(keys[i]) for idx in groups.values() for i in idx)
    assert seen == keys
    for sid, idx in groups.items():
        assert all(r.shard_of(int(keys[int(i)])) == sid for i in idx)


def test_router_balance_and_range():
    r = ShardRouter(4, vnodes=64, seed=0)
    loads = r.load(np.arange(4000, dtype=np.int64))
    assert loads.sum() == 4000 and (loads > 0).all()
    assert loads.max() / loads.min() < 3.0


def test_router_single_shard_fast_path():
    r = ShardRouter(1, seed=9)
    assert r.shard_of(123) == 0
    assert (r.shard_of_many(np.arange(50)) == 0).all()


def test_router_grow_moves_keys_only_to_new_shard():
    keys = np.arange(3000, dtype=np.int64)
    before = ShardRouter(4, seed=7).shard_of_many(keys)
    after = ShardRouter(5, seed=7).shard_of_many(keys)
    moved = before != after
    assert 0 < moved.sum() < len(keys)
    assert (after[moved] == 4).all()


# ----------------------------------------------------------------------
# shard protocol
def _shard(**kw) -> CacheShard:
    cfg = ShardConfig(shard_id=0, n_shards=1, cache_bytes=200_000,
                      split=SPLIT, **kw)
    return CacheShard(cfg)


def test_shard_handles_unknown_op_and_bad_args():
    shard = _shard()
    resp = shard.handle(Request("warp"))
    assert not resp.ok and "warp" in resp.error
    resp = shard.handle(Request("lookup", ()))   # missing args -> error
    assert not resp.ok and isinstance(resp, Response)
    shard.close()
    shard.close()


def test_shard_roundtrip_and_stats():
    shard = _shard()
    arr = np.arange(12, dtype=np.float32)
    ok = shard.handle(Request("insert",
                              (5, "decoded", arr, arr.nbytes, False)))
    assert ok.ok and ok.value
    form, value, tier = shard.handle(Request("lookup", (5,))).value
    assert form == "decoded" and tier == "dram"
    assert np.array_equal(value, arr)
    stats = shard.handle(Request("stats", ())).value
    assert stats["shard"] == 0 and stats["entries"] == 1
    assert stats["bytes_used"] == arr.nbytes
    shard.close()


# ----------------------------------------------------------------------
# ShardedCache over the sim transport
def test_sharded_cache_surface_matches_local():
    c = ShardedCache(400_000, SPLIT, shards=3, seed=0)
    arr = np.arange(24, dtype=np.float32)
    for k in range(12):
        assert c.insert(k, "decoded", arr, arr.nbytes)
    assert c.form_of(3) == "decoded" and c.form_of(99) is None
    assert c.contains("decoded", 3) and not c.contains("encoded", 3)
    assert c.contains_many("decoded", range(12)) == [True] * 12
    assert c.serving_forms([3, 99]) == ["decoded", None]
    form, value, tier = c.lookup_tiered(3)
    assert form == "decoded" and tier == "dram"
    assert np.array_equal(value, arr)
    assert c.total_capacity("decoded") > 0
    assert sum(c.total_capacity(f) for f in FORMS) <= 400_000
    assert c.bytes_used() == 12 * arr.nbytes
    status = c.status_array(16)
    assert (status[:12] > 0).all() and (status[12:] == 0).all()
    assert c.evict(3, "decoded") and c.form_of(3) is None
    assert c.hit_rate() > 0
    v0 = c.version
    c.resize((0.1, 0.45, 0.45))
    assert c.split == (0.1, 0.45, 0.45)
    assert c.version >= v0
    c.close()
    c.close()       # idempotent


def test_sharded_cache_piggybacks_evictions():
    # chain-terminal evictions (spill overflow) must piggyback across
    # the transport exactly like the local cache's take_evicted; a tiny
    # spill level under an LRU DRAM tier guarantees overflow
    root = tempfile.mkdtemp(prefix="seneca-piggyback-")
    pol = {"encoded": "lru", "decoded": "lru", "augmented": "lru"}
    c = ShardedCache(3_000, SPLIT, evict_policies=pol,
                     spill_bytes=2_000, spill_dir=root, spill_split=SPLIT,
                     shards=2, seed=0)
    for k in range(64):
        c.insert(k, "decoded", np.full(64, k, np.uint8), 64)
    assert c.has_pending_evicted()
    dropped = set(c.take_evicted())
    assert dropped and not c.has_pending_evicted()
    # every piggybacked key is really gone from its owning shard
    assert not any(c.contains_many("decoded", sorted(dropped)))
    c.close()
    os.rmdir(root)


def test_sharded_cache_spill_subdirs_cleaned():
    root = tempfile.mkdtemp(prefix="seneca-shard-spill-")
    pol = {"encoded": "lru", "decoded": "lru", "augmented": "lru"}
    c = ShardedCache(4_000, SPLIT, evict_policies=pol,
                     spill_bytes=200_000, spill_dir=root,
                     spill_split=SPLIT, shards=2, seed=0)
    assert c.has_spill
    for k in range(64):
        c.insert(k, "decoded", np.full(64, k, np.uint8), 64)
    assert c.disk_bytes_used() > 0          # DRAM overflow demoted
    spill = c.spill_stats()
    assert spill and sum(d.get("disk_entries", 0)
                         for d in spill.values()) > 0
    c.close()
    assert os.listdir(root) == []            # per-shard subdirs removed
    os.rmdir(root)


def test_sharded_cache_needs_split_or_profiles():
    with pytest.raises(ValueError, match="split or profiles"):
        ShardedCache(1_000, None, shards=2)
    with pytest.raises(ValueError, match="shards"):
        ShardedCache(1_000, SPLIT, shards=0)


def test_sharded_produce_and_ingest_sim():
    ds = tiny(n=48)
    c = ShardedCache(2 * 48 * ds.augmented_bytes(), SPLIT, shards=2,
                     seed=0, dataset=ds)
    out = np.asarray(c.produce(7, epoch_tag=2))
    img = ds.decode(ds.encoded(7), 7)
    ref = augment_np(img, ds.crop_hw,
                     np.random.default_rng(produce_seed(2, 7)))
    assert np.array_equal(out, ref)
    assert c.ingest(range(48), epoch_tag=2) == 48
    ss = c.shard_stats()
    assert sum(s["produced"] for s in ss) == 49
    assert all(s["produced"] > 0 for s in ss)
    c.close()


# ----------------------------------------------------------------------
# server integration
def test_server_sharded_session_and_stats():
    ds = tiny(n=64)
    server = SenecaServer.for_dataset(ds, cache_frac=0.5, seed=0,
                                      shards=2)
    with server.open_session(batch_size=16) as sess:
        pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2)
        for _ in range(6):       # > 1 epoch: admissions + shard lookups
            batch = pipe.next_batch()
            assert batch["images"].shape[0] == 16
        stats = sess.stats()
        pipe.stop()
    assert len(stats["shards"]) == 2
    assert {s["shard"] for s in stats["shards"]} == {0, 1}
    assert sum(s["entries"] for s in stats["shards"]) > 0
    server.close()
    server.close()      # idempotent


def test_virtual_clock_rejects_process_transport():
    ds = tiny(n=32)
    server = SenecaServer.for_dataset(ds, cache_frac=0.5, seed=0,
                                      shards=2)
    # the guard keys off the cache's transport tag — no need to spawn
    server.service.cache.transport_name = "process"
    with pytest.raises(ValueError, match="sim"):
        WorkloadRunner(server, RemoteStorage(ds), clock=VirtualClock())
    server.close()


def _sharded_workload_ids(shards: int, seed: int = 0):
    ds = tiny(n=64)
    server = SenecaServer.for_dataset(
        ds, cache_bytes=2 * ds.n_samples * ds.augmented_bytes(),
        split=SPLIT, seed=seed, shards=shards)
    runner = deterministic_runner(server, RemoteStorage(ds), seed=seed)
    res = runner.run([
        JobSpec("a", arrival_s=0.0, epochs=1, batch_size=16,
                gpu_rate=1000),
        JobSpec("b", arrival_s=0.05, epochs=1, batch_size=8,
                gpu_rate=500),
    ], timeout=120)
    ids = {j.spec.name: list(j.sample_ids) for j in res.jobs}
    server.close()
    return ids


def test_sharded_sim_runs_are_deterministic():
    """The tier-1 acceptance gate: the same trace on shards=1 (classic
    engine) and shards=2 (sim transport) yields identical per-job
    sample-id sequences, and shards=2 is reproducible run to run."""
    one = _sharded_workload_ids(1)
    two = _sharded_workload_ids(2)
    two_again = _sharded_workload_ids(2)
    assert two == two_again
    assert one == two
    assert all(len(v) == 64 for v in one.values())


# ----------------------------------------------------------------------
# process transport
def test_process_transport_roundtrip_and_close():
    ds = tiny(n=32)
    c = ShardedCache(2 * 32 * ds.augmented_bytes(), SPLIT, shards=2,
                     transport="process", seed=0, dataset=ds)
    xchg = c._xchg
    try:
        arr = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
        assert c.insert(40, "decoded", arr, arr.nbytes)
        form, value, tier = c.lookup_tiered(40)
        assert form == "decoded" and tier == "dram"
        assert np.array_equal(np.asarray(value), arr)
        out = np.asarray(c.produce(9, epoch_tag=3))
        img = ds.decode(ds.encoded(9), 9)
        ref = augment_np(img, ds.crop_hw,
                         np.random.default_rng(produce_seed(3, 9)))
        assert np.array_equal(out, ref)   # cross-process byte parity
        assert c.ingest(range(32), epoch_tag=1) == 32
    finally:
        c.close()
        c.close()
    assert not os.path.exists(xchg)


@pytest.mark.parametrize("shards", [1, 2])
def test_process_transport_rejects_device_cache(shards):
    """The HBM tier belongs in the process that holds the accelerator:
    a device cache on process shards is refused at construction, before
    any shard process is spawned."""
    before = set(glob.glob(os.path.join(tempfile.gettempdir(),
                                        "seneca-xchg-*")))
    with pytest.raises(ValueError, match="shard_transport='process'"):
        SenecaServer.for_dataset(tiny(n=32), device_cache_bytes=1 << 20,
                                 shards=shards, shard_transport="process")
    after = set(glob.glob(os.path.join(tempfile.gettempdir(),
                                       "seneca-xchg-*")))
    assert after == before


def test_process_transport_failed_start_cleans_up():
    before = set(glob.glob(os.path.join(tempfile.gettempdir(),
                                        "seneca-xchg-*")))
    with pytest.raises(Exception):
        # lambdas cannot pickle to a spawned shard: start must fail,
        # tear the fleet down, and leave no exchange dir behind
        ShardedCache(10_000, SPLIT, shards=2, transport="process",
                     dataset=lambda: None)
    after = set(glob.glob(os.path.join(tempfile.gettempdir(),
                                       "seneca-xchg-*")))
    assert after == before


# ----------------------------------------------------------------------
# close() idempotence on the classic engine (satellite)
def test_tiered_cache_close_idempotent_with_spill():
    root = tempfile.mkdtemp(prefix="seneca-close-")
    cache = TieredCache(4_000, SPLIT, spill_bytes=50_000, spill_dir=root,
                        spill_split=SPLIT)
    cache.insert(1, "decoded", np.zeros(900, np.uint8), 900)
    cache.insert(2, "decoded", np.zeros(900, np.uint8), 900)
    cache.close()
    assert not any(files for _p, _d, files in os.walk(root))
    cache.close()       # second close: no raise, no re-created files
    assert not any(files for _p, _d, files in os.walk(root))
    os.rmdir(root)
