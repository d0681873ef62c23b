"""Open-loop serving + the clock-correctness bugfixes that make its
latency accounting exact.

Covers the ISSUE-9 contract: exact nearest-rank percentile math;
seed-reproducible arrival schedules; a VirtualClock burst trace whose
per-request latencies (and p99) are identical across two fresh runs —
with storage-stall time flowing through the clock-aware token bucket;
an overload trace where SLO admission control sheds/degrades instead of
growing the queue without bound; and regressions for the three
satellite bugfixes (token-bucket pacing through the pluggable clock,
repartition cooldown on the service clock, sub-poll ``get`` timeouts).
"""
import queue
import time

import numpy as np
import pytest

from repro.api import SLO, SenecaServer
from repro.api.telemetry import TelemetryAggregator, quantile
from repro.data.pipeline import DSIPipeline
from repro.data.storage import BandwidthBudget, RemoteStorage
from repro.data.synthetic import SyntheticDataset, tiny
from repro.workload import (OpenLoopGenerator, VirtualClock,
                            bursty_arrivals, diurnal_arrivals,
                            make_arrivals, poisson_arrivals)


def _server(ds, **kw):
    kw.setdefault("cache_frac", 0.3)
    kw.setdefault("seed", 0)
    return SenecaServer.for_dataset(ds, **kw)


# ----------------------------------------------------------------------
# percentile math (exact nearest-rank quantiles)
def test_quantile_exact_on_known_samples():
    xs = list(range(1, 101))            # 1..100
    assert quantile(xs, 0.50) == 50
    assert quantile(xs, 0.99) == 99
    assert quantile(xs, 0.999) == 100
    assert quantile(xs, 1.0) == 100
    assert quantile(xs, 0.0) == 1       # nearest-rank floor: min(ceil)=1


def test_quantile_is_always_an_observed_sample():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0]
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert quantile(xs, q) in xs
    assert quantile([7.5], 0.99) == 7.5


def test_quantile_rejects_bad_input():
    with pytest.raises(ValueError):
        quantile([], 0.5)
    with pytest.raises(ValueError):
        quantile([1.0], 1.5)
    with pytest.raises(ValueError):
        quantile([1.0], -0.1)


# ----------------------------------------------------------------------
# arrival schedules
def test_arrivals_seed_reproducible_and_sorted():
    for proc in ("poisson", "bursty", "diurnal"):
        a = make_arrivals(proc, rate=200.0, n=300, seed=5)
        b = make_arrivals(proc, rate=200.0, n=300, seed=5)
        c = make_arrivals(proc, rate=200.0, n=300, seed=6)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)
        assert np.all(np.diff(a) >= 0) and a.shape == (300,)


def test_poisson_mean_rate_roughly_right():
    a = poisson_arrivals(100.0, n=5_000, seed=0)
    assert a[-1] == pytest.approx(50.0, rel=0.1)   # n/rate seconds


def test_arrival_validation():
    with pytest.raises(ValueError):
        make_arrivals("weibull", 10.0, 10)
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 10)
    with pytest.raises(ValueError):
        bursty_arrivals(10.0, 10, burst_factor=8.0, duty=0.25)  # >= 1/duty
    with pytest.raises(ValueError):
        diurnal_arrivals(10.0, 10, depth=1.5)


# ----------------------------------------------------------------------
# SLO config
def test_slo_validation():
    SLO(p99_target_s=0.1)
    with pytest.raises(ValueError):
        SLO(p99_target_s=0.0)
    with pytest.raises(ValueError):
        SLO(p99_target_s=0.1, max_queue=0)
    with pytest.raises(ValueError):
        SLO(p99_target_s=0.1, degrade_frac=0.9, encode_frac=0.5)


# ----------------------------------------------------------------------
# telemetry request accounting
def test_record_request_counters_and_summary():
    tel = TelemetryAggregator()
    tel.record_request("shed")
    tel.record_request("served", total_s=0.010,
                       phases={"queue": 0.002, "fetch": 0.008})
    tel.record_request("degraded", total_s=0.030, phases={"queue": 0.030})
    with pytest.raises(ValueError):
        tel.record_request("lost")
    summary = tel.request_summary()
    assert summary["outcomes"] == {"served": 1, "degraded": 1,
                                   "encoded": 0, "shed": 1}
    assert summary["completed"] == 2
    assert summary["latency_s"]["p50"] == 0.010
    assert summary["latency_s"]["p99"] == 0.030
    assert summary["phase_latency_s"]["queue"]["p99"] == 0.030
    # the additive stats key only appears once requests exist
    assert "requests" in tel.as_dict()
    assert "requests" not in TelemetryAggregator().as_dict()


# ----------------------------------------------------------------------
# open-loop serving under VirtualClock
def _run_open_loop(arrivals, slo, *, seed=0, n_samples=96):
    ds = tiny(n=n_samples)
    server = _server(ds)
    clock = VirtualClock()
    storage = RemoteStorage(ds, bandwidth=4e6, clock=clock)
    gen = OpenLoopGenerator(server, storage, clock=clock, slo=slo,
                            n_workers=2, seed=seed,
                            phase_costs={"decode": 0.004,
                                         "augment": 0.003})
    res = gen.run(arrivals)
    stats = server.stats()
    server.close()
    return res, stats


def test_virtual_clock_burst_trace_deterministic_p99():
    arrivals = bursty_arrivals(rate=350.0, n=250, seed=11)
    r1, _ = _run_open_loop(arrivals, None)
    r2, _ = _run_open_loop(arrivals, None)
    lat1 = [(r.req_id, r.total_s, r.queue_s, r.fetch_s, r.decode_s,
             r.augment_s, r.outcome) for r in r1.requests]
    lat2 = [(r.req_id, r.total_s, r.queue_s, r.fetch_s, r.decode_s,
             r.augment_s, r.outcome) for r in r2.requests]
    assert lat1 == lat2                       # per-request, bit-for-bit
    assert r1.percentiles() == r2.percentiles()
    assert r1.percentiles()["p99"] > 0
    # storage stalls flowed through the clock-aware bucket: some fetch
    # phase time must exist even though compute is free in virtual time
    assert any(r.fetch_s > 0 for r in r1.requests)


def test_overload_sheds_instead_of_queueing_unboundedly():
    arrivals = poisson_arrivals(500.0, n=400, seed=3)   # ~1.75x capacity
    slo = SLO(p99_target_s=0.05, max_queue=64)
    uncontrolled, _ = _run_open_loop(arrivals, None)
    controlled, stats = _run_open_loop(arrivals, slo)
    assert uncontrolled.counts["shed"] == 0
    c = controlled.counts
    assert c["shed"] > 0                      # load was actually shed
    assert c["shed"] + c["degraded"] + c["encoded"] + c["served"] == 400
    # the whole point: the tail is held far below the uncontrolled run
    assert controlled.percentiles()["p99"] \
        < uncontrolled.percentiles()["p99"]
    # queue wait (the unbounded-growth signal) is bounded too
    assert max(r.queue_s for r in controlled.completed) \
        < max(r.queue_s for r in uncontrolled.completed)
    # decisions surface in stats(), not just the ServeResult
    req = stats["telemetry"]["requests"]
    assert req["outcomes"]["shed"] == c["shed"]
    assert req["latency_s"]["p99"] > 0


def test_degrade_caps_work_not_cached_quality():
    """A request admitted at encoded level still gets the augmented form
    when the cache already holds it."""
    ds = tiny(n=8)
    server = _server(ds, cache_frac=1.0)
    clock = VirtualClock()
    storage = RemoteStorage(ds, clock=clock)
    # warm every sample to augmented via an uncontrolled pass
    gen = OpenLoopGenerator(server, storage, clock=clock, slo=None,
                            n_workers=1, seed=0)
    warm = gen.run(np.linspace(0.001, 0.02, 16),
                   sample_ids=list(range(8)) * 2)
    assert all(r.outcome == "served" for r in warm.requests)
    # now a fresh generator whose SLO sheds nothing but degrades
    # everything (encode_frac tiny => every queued request degrades)
    gen2 = OpenLoopGenerator(server, storage, clock=VirtualClock(),
                             slo=SLO(p99_target_s=1.0), n_workers=1,
                             seed=0)
    res = gen2.run(np.linspace(0.001, 0.01, 8),
                   sample_ids=list(range(8)))
    # cache hits at augmented form serve full quality regardless of level
    assert all(r.outcome == "served" and r.form == "augmented"
               for r in res.requests)
    server.close()


# ----------------------------------------------------------------------
# satellite bugfix: token bucket paces on the pluggable clock
def test_bandwidth_budget_charges_virtual_time():
    clock = VirtualClock()
    ticket = clock.register()
    clock.bind(ticket)
    try:
        budget = BandwidthBudget(1000.0, clock=clock)
        wall0 = time.monotonic()
        stall = budget.consume(5000)
        assert stall == pytest.approx(5.0)
        assert clock.now() == pytest.approx(5.0)       # virtual seconds
        assert time.monotonic() - wall0 < 1.0          # not wall seconds
        # degrade takes effect at the correct virtual instant: the next
        # transfer is priced at the post-change rate from virtual now
        budget.rate = 100.0
        budget.consume(1000)
        assert clock.now() == pytest.approx(15.0)
    finally:
        clock.unbind()
        clock.unregister(ticket)


def test_bandwidth_budget_wall_clock_default_unchanged():
    budget = BandwidthBudget(1e9)          # no clock: historical behavior
    assert budget.clock is None
    t0 = time.monotonic()
    budget.consume(1000)                   # 1us pacing, returns promptly
    assert time.monotonic() - t0 < 0.5
    assert budget.bytes_served == 1000


def test_remote_storage_degrade_with_virtual_clock():
    ds = tiny(n=16)
    clock = VirtualClock()
    ticket = clock.register()
    clock.bind(ticket)
    try:
        storage = RemoteStorage(ds, bandwidth=1e6, clock=clock)
        storage.fetch(0)
        t_normal = clock.now()
        storage.degrade(0.1)               # 10x slower from this instant
        storage.fetch(1)
        t_degraded = clock.now() - t_normal
        storage.restore_bandwidth()
        assert t_degraded > 5 * t_normal   # collapse shaped virtual time
        assert storage.degraded_fetches == 1
    finally:
        clock.unbind()
        clock.unregister(ticket)


# ----------------------------------------------------------------------
# satellite bugfix: repartition cooldown on the service clock
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t


def test_repartition_cooldown_uses_service_clock():
    ds = tiny(n=64)
    server = _server(ds, repartition="adaptive",
                     repartition_cooldown=100.0)
    ctl = server.service.controller
    fake = _FakeClock()
    server.service.set_clock(fake)
    ctl.tick()
    first_tick = ctl._last_tick
    assert first_tick == 0.0               # stamped in clock time
    fake.t = 50.0                          # inside the cooldown window
    ctl.tick()
    assert ctl._last_tick == first_tick    # gated, regardless of wall time
    fake.t = 150.0                         # cooldown elapsed (clock time)
    ctl.tick()
    assert ctl._last_tick == 150.0
    server.close()


# ----------------------------------------------------------------------
# satellite bugfix: sub-poll timeouts no longer overshoot
def test_per_sample_get_honors_sub_poll_timeout():
    ds = tiny(n=32)
    server = _server(ds)
    pipe = DSIPipeline(server.open_session(batch_size=8),
                       RemoteStorage(ds))
    try:
        # prefetch never started: the queue stays empty, so get() must
        # raise at ~the 50ms deadline, not after a full 200ms poll
        t0 = time.monotonic()
        with pytest.raises(queue.Empty):
            pipe.get(timeout=0.05)
        assert time.monotonic() - t0 < 0.15
    finally:
        pipe.stop()
        server.close()


class _SlowStorage(RemoteStorage):
    """Every fetch takes ~0.3s of wall time (a route cannot build a
    batch of 8 inside a 50ms get timeout).  The slowness sits in the
    storage, not the dataset: the device route takes only the plain
    counter-hash SyntheticDataset."""

    def fetch(self, sample_id: int) -> bytes:
        time.sleep(0.3)
        return super().fetch(sample_id)


def test_device_prefetch_get_honors_sub_poll_timeout():
    ds = SyntheticDataset("slow", 32, 24_000, image_hw=(64, 64),
                          crop_hw=(56, 56), n_classes=100)
    server = _server(ds)
    pipe = DSIPipeline(server.open_session(batch_size=8),
                       _SlowStorage(ds), executor="device")
    try:
        pipe.start_prefetch()
        t0 = time.monotonic()
        with pytest.raises(queue.Empty):
            pipe.get(timeout=0.05)
        assert time.monotonic() - t0 < 0.15
        assert pipe._thread.is_alive()          # still fetching
    finally:
        pipe.stop()
        server.close()
