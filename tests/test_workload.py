"""Live multi-job workload runner + deterministic-clock concurrency.

Covers the ISSUE-4 contract: the VirtualClock serializes participants in
``(wake_time, ticket)`` order and advances deterministically; two
virtual-clock runs of the same trace produce identical per-job sample-id
sequences and identical makespans; the live stack's hit rate agrees with
the :class:`DSISimulator` on the same 2-job trace (tying the runner to
the Fig. 8 model); arrivals/epoch accounting/cancellation behave; and
the private-server baseline mode works (the fig_live_makespan shape).
"""
import threading
import time

import numpy as np
import pytest

from repro.api import (AZURE_NC96, DSISimulator, DatasetProfile, JobSpec,
                       SENECA, SenecaServer, SimJob, VirtualClock,
                       WorkloadRunner)
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny
from repro.workload.clock import RealClock


def _server(ds, **kw):
    kw.setdefault("cache_frac", 0.4)
    kw.setdefault("seed", 0)
    return SenecaServer.for_dataset(ds, **kw)


# ----------------------------------------------------------------------
# VirtualClock semantics
def test_virtual_clock_serializes_in_wake_order():
    clock = VirtualClock()
    t0, t1, t2 = clock.register(), clock.register(), clock.register()
    order = []
    lock = threading.Lock()

    def body(ticket, wakes):
        for w in wakes:
            now = clock.sleep_until(ticket, w)
            with lock:
                order.append((now, ticket))
        clock.unregister(ticket)

    # same wake time 1.0 for tickets 0 and 1 -> ticket order breaks the
    # tie; ticket 2 wakes earlier and again later
    threads = [threading.Thread(target=body, args=args) for args in
               ((t0, [1.0, 3.0]), (t1, [1.0, 2.0]), (t2, [0.5, 5.0]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads)
    assert order == [(0.5, t2), (1.0, t0), (1.0, t1), (2.0, t1),
                     (3.0, t0), (5.0, t2)]
    assert clock.now() == 5.0


def test_virtual_clock_never_goes_backwards():
    clock = VirtualClock(start=10.0)
    t = clock.register()
    done = []

    def body():
        # asking to wake in the past clamps to the current virtual time
        done.append(clock.sleep_until(t, 3.0))
        clock.unregister(t)

    th = threading.Thread(target=body)
    th.start()
    th.join(timeout=10.0)
    assert done == [10.0]


def test_virtual_clock_unregistered_ticket_rejected():
    clock = VirtualClock()
    with pytest.raises(RuntimeError, match="not registered"):
        clock.sleep_until(99, 1.0)


def test_virtual_clock_interrupt_unblocks():
    clock = VirtualClock()
    t0, t1 = clock.register(), clock.register()   # t1 never sleeps
    stop = threading.Event()
    out = []

    def body():
        out.append(clock.sleep_until(t0, 1.0, interrupt=stop))
        clock.unregister(t0)

    th = threading.Thread(target=body)
    th.start()
    time.sleep(0.1)
    stop.set()
    th.join(timeout=10.0)
    assert not th.is_alive(), "interrupted sleep must not deadlock"
    clock.unregister(t1)


# ----------------------------------------------------------------------
# runner basics (real clock)
def test_runner_epochs_coverage_and_arrival_order():
    ds = tiny(n=96)
    server = _server(ds, use_ods=False)     # naive: exact epoch coverage
    runner = WorkloadRunner(server, RemoteStorage(ds))
    res = runner.run([
        JobSpec("a", arrival_s=0.0, epochs=2, batch_size=12,
                gpu_rate=4000, n_workers=2),
        JobSpec("b", arrival_s=0.2, epochs=1, batch_size=12,
                gpu_rate=4000, n_workers=2),
    ], timeout=120)
    server.close()
    assert res.ok
    a, b = res.job("a"), res.job("b")
    assert a.samples == 2 * 96 and a.epochs_completed == 2
    assert b.samples == 96 and b.epochs_completed == 1
    assert b.start_s >= 0.2 > a.start_s
    # naive sampler serves the epoch permutation exactly: each epoch
    # covers every sample once
    for job in (a, b):
        for e in range(job.epochs_completed):
            epoch_ids = job.sample_ids[e * 96:(e + 1) * 96]
            assert sorted(epoch_ids) == list(range(96))
    assert res.makespan >= max(a.end_s, b.end_s)
    assert res.stats["n_sessions"] == 0          # all sessions closed


def test_runner_gpu_rate_paces_consumption():
    ds = tiny(n=64)
    server = _server(ds)
    runner = WorkloadRunner(server, RemoteStorage(ds))
    # 64 samples at 160/s >= 0.4s even though production is instant
    res = runner.run([JobSpec("slow", epochs=1, batch_size=16,
                              gpu_rate=160, n_workers=2)], timeout=120)
    server.close()
    assert res.ok
    assert res.jobs[0].duration_s >= 0.35
    assert res.wall_s >= 0.35


def test_runner_cancel_joins_promptly():
    ds = tiny(n=256)
    server = _server(ds)
    runner = WorkloadRunner(server, RemoteStorage(ds), record_ids=False)
    trace = [JobSpec(f"j{i}", epochs=50, batch_size=16, gpu_rate=300,
                     n_workers=2) for i in range(2)]
    threading.Timer(0.4, runner.cancel).start()
    res = runner.run(trace, timeout=60, raise_on_error=False)
    server.close()
    assert all(j.cancelled for j in res.jobs)
    assert res.wall_s < 30.0
    assert not res.ok


def test_runner_validates_trace_and_construction():
    ds = tiny(n=32)
    server = _server(ds)
    storage = RemoteStorage(ds)
    with pytest.raises(ValueError, match="exactly one"):
        WorkloadRunner(server, storage, server_factory=lambda s: server)
    with pytest.raises(ValueError, match="exactly one"):
        WorkloadRunner(storage=storage)
    runner = WorkloadRunner(server, storage)
    with pytest.raises(ValueError, match="empty workload"):
        runner.run([])
    with pytest.raises(ValueError, match="duplicate job names"):
        runner.run([JobSpec("x"), JobSpec("x")])
    with pytest.raises(ValueError, match="epochs"):
        JobSpec("bad", epochs=0)
    with pytest.raises(ValueError, match="gpu_rate"):
        JobSpec("bad", gpu_rate=0.0)
    with pytest.raises(ValueError, match="unknown executor"):
        JobSpec("bad", executor="warp-speed")   # fails at spec time,
    #   not inside a job thread with a session already open
    # virtual clock runs only the per-sample reference route
    vrunner = WorkloadRunner(server, storage, clock=VirtualClock())
    with pytest.raises(ValueError, match="per-sample"):
        vrunner.run([JobSpec("dev", executor="device")])
    server.close()


def test_runner_job_error_surfaces_after_join():
    ds = tiny(n=64)
    server = _server(ds)

    class BrokenStorage(RemoteStorage):
        def fetch(self, sample_id):
            raise IOError("storage down")

    runner = WorkloadRunner(server, BrokenStorage(ds))
    with pytest.raises(RuntimeError, match="workload jobs failed"):
        runner.run([JobSpec("a", epochs=1, batch_size=8, n_workers=1)],
                   timeout=60)
    res = runner.run([JobSpec("a", epochs=1, batch_size=8, n_workers=1)],
                     timeout=60, raise_on_error=False)
    assert res.jobs[0].error is not None and not res.ok
    server.close()
    assert server.service.backend.n_jobs >= 1   # no crash on teardown


def test_server_run_workload_convenience():
    ds = tiny(n=64)
    server = _server(ds)
    # timeout/raise_on_error forward to run() (review finding: they
    # used to TypeError against the constructor)
    res = server.run_workload(
        [JobSpec("a", epochs=1, batch_size=16, n_workers=2)],
        RemoteStorage(ds), record_ids=False, timeout=120,
        raise_on_error=True)
    server.close()
    assert res.ok and res.total_samples == 64
    assert res.stats is not None


def test_pipeline_construction_failure_closes_session(monkeypatch):
    """If DSIPipeline construction raises after the session opened, the
    session must still close — a phantom job would inflate the eviction
    threshold and repartition triggers forever (review finding)."""
    import repro.workload.runner as runner_mod
    ds = tiny(n=64)
    server = _server(ds)

    def boom(*a, **kw):
        raise RuntimeError("pipeline ctor boom")

    monkeypatch.setattr(runner_mod, "DSIPipeline", boom)
    runner = WorkloadRunner(server, RemoteStorage(ds))
    res = runner.run([JobSpec("a", epochs=1, batch_size=8)],
                     timeout=60, raise_on_error=False)
    assert res.jobs[0].error is not None
    assert server.n_sessions == 0, "leaked session after ctor failure"
    server.close()


# ----------------------------------------------------------------------
# ISSUE-4 satellite: virtual-clock determinism
def _virtual_run(n=128, seed=0):
    ds = tiny(n=n)
    server = _server(ds, seed=seed)
    runner = WorkloadRunner(server, RemoteStorage(ds),
                            clock=VirtualClock(), seed=seed)
    res = runner.run([
        JobSpec("a", arrival_s=0.0, epochs=2, batch_size=16,
                gpu_rate=1000),
        JobSpec("b", arrival_s=0.05, epochs=2, batch_size=16,
                gpu_rate=500),
        JobSpec("c", arrival_s=0.10, epochs=1, batch_size=8,
                gpu_rate=2000),
    ], timeout=300)
    stats = res.stats
    server.close()
    return res, stats


def test_virtual_clock_runs_are_deterministic():
    """Two runs of the same trace: identical per-job sample-id sequences
    AND identical makespan (the non-flaky-concurrency guarantee)."""
    res1, stats1 = _virtual_run()
    res2, stats2 = _virtual_run()
    for j1, j2 in zip(res1.jobs, res2.jobs):
        assert j1.sample_ids == j2.sample_ids, j1.spec.name
        assert j1.epoch_ends == j2.epoch_ends, j1.spec.name
        assert j1.end_s == j2.end_s
    assert res1.makespan == res2.makespan
    assert stats1["ods_hit_rate"] == stats2["ods_hit_rate"]
    assert stats1["substitutions"] == stats2["substitutions"]
    assert res1.clock == "virtual"


def test_virtual_clock_deterministic_with_hbm_tier():
    """Determinism holds with the device cache tier enabled: HBM
    admission/promotion must not introduce ordering races into
    virtual-clock runs (same trace -> same ids, ends, makespan)."""
    def run():
        ds = tiny(n=128)
        server = _server(
            ds, device_cache_bytes=int(0.3 * 128 * ds.augmented_bytes()))
        runner = WorkloadRunner(server, RemoteStorage(ds),
                                clock=VirtualClock(), seed=0)
        res = runner.run([
            JobSpec("a", arrival_s=0.0, epochs=2, batch_size=16,
                    gpu_rate=1000),
            JobSpec("b", arrival_s=0.05, epochs=2, batch_size=16,
                    gpu_rate=500),
        ], timeout=300)
        stats = res.stats
        server.close()
        return res, stats

    res1, stats1 = run()
    res2, stats2 = run()
    for j1, j2 in zip(res1.jobs, res2.jobs):
        assert j1.sample_ids == j2.sample_ids, j1.spec.name
        assert j1.epoch_ends == j2.epoch_ends, j1.spec.name
        assert j1.end_s == j2.end_s
    assert res1.makespan == res2.makespan
    assert stats1["ods_hit_rate"] == stats2["ods_hit_rate"]


def test_virtual_clock_interleaving_respects_rates():
    """Faster-ingest jobs finish earlier; epoch ends are monotone; the
    makespan is the slowest job's end (all in virtual seconds)."""
    res, _stats = _virtual_run()
    a, b, c = res.job("a"), res.job("b"), res.job("c")
    assert res.ok
    # b ingests at half a's rate over the same 2 epochs: finishes last
    assert b.end_s == res.makespan > a.end_s
    for j in res.jobs:
        assert j.epoch_ends == sorted(j.epoch_ends)
        assert j.samples == j.spec.epochs * 128
    # virtual makespan is pacing-determined: 2 epochs * 128 / 500 + 0.05
    assert b.end_s == pytest.approx(0.05 + 256 / 500, abs=1e-9)


# ----------------------------------------------------------------------
# ISSUE-4 satellite: cross-validation against the fluid simulator
def test_live_virtual_run_matches_simulator_hit_rate():
    """WorkloadRunner (virtual clock) and DSISimulator on the same 2-job
    trace agree on the serve-level cache hit rate — the live stack is
    tied to the same model Fig. 8 validates."""
    n, batch, epochs, rate = 256, 16, 2, 2000
    ds = tiny(n=n)
    cache_bytes = int(0.35 * n * ds.augmented_bytes())

    server = SenecaServer.for_dataset(ds, cache_bytes=cache_bytes, seed=0)
    runner = WorkloadRunner(server, RemoteStorage(ds),
                            clock=VirtualClock(), record_ids=False)
    res = runner.run([JobSpec("a", 0.0, epochs, batch, rate),
                      JobSpec("b", 0.0, epochs, batch, rate)],
                     timeout=300)
    # serve-level hit rate: fraction of pipeline lookups answered by any
    # cache tier (the simulator's hits/misses count the same event)
    hit_rates = res.stats["telemetry"]["hit_rates"]
    live_hit = 1.0 - hit_rates.get("storage", 0.0)
    server.close()
    assert res.ok

    profile = DatasetProfile(ds.name, n, ds.mean_encoded_bytes,
                             decoded_bytes=ds.decoded_bytes(),
                             augmented_bytes=ds.augmented_bytes())
    sim = DSISimulator(AZURE_NC96, profile, SENECA,
                       cache_bytes=cache_bytes, seed=0)
    sim_res = sim.run([SimJob(0, gpu_rate=rate, batch_size=batch,
                              epochs=epochs),
                       SimJob(1, gpu_rate=rate, batch_size=batch,
                              epochs=epochs)])
    # both sides are deterministic (virtual clock / seeded sim): the
    # tolerance absorbs modelling differences (refill policy, admission
    # timing), not run-to-run noise
    assert live_hit == pytest.approx(sim_res.hit_rate, abs=0.12), \
        f"live={live_hit:.3f} sim={sim_res.hit_rate:.3f}"
    assert live_hit > 0.5 and sim_res.hit_rate > 0.5


# ----------------------------------------------------------------------
# private-server baseline mode (the fig_live_makespan shape)
def test_private_server_factory_mode():
    ds = tiny(n=64)
    storage = RemoteStorage(ds)
    made = []

    def factory(spec):
        srv = _server(ds, use_ods=False, split=(1.0, 0.0, 0.0),
                      eviction="lru")
        made.append(srv)
        return srv

    runner = WorkloadRunner(server_factory=factory, storage=storage)
    res = runner.run([JobSpec("a", epochs=1, batch_size=16, n_workers=2),
                      JobSpec("b", epochs=1, batch_size=16, n_workers=2)],
                     timeout=120)
    assert res.ok and len(made) == 2
    assert res.stats is None                    # no shared server
    for j in res.jobs:
        assert j.stats is not None              # per-job private stats
        assert j.stats["n_sessions"] == 0
    # private servers see only their own job
    assert all(s.n_sessions == 0 for s in made)


def test_real_clock_sleep_until_interruptible():
    clock = RealClock()
    t = clock.register()
    stop = threading.Event()
    stop.set()
    t0 = time.monotonic()
    clock.sleep_until(t, time.monotonic() + 5.0, interrupt=stop)
    assert time.monotonic() - t0 < 1.0
    clock.unregister(t)


def test_pipeline_consume_hook_fires_per_batch():
    from repro.data.pipeline import DSIPipeline
    ds = tiny(n=32)
    server = _server(ds)
    calls = []
    pipe = DSIPipeline(server.open_session(batch_size=8),
                       RemoteStorage(ds), n_workers=1,
                       consume_hook=lambda b: calls.append(
                           b["ids"].tolist()))
    got = [pipe.next_batch()["ids"].tolist() for _ in range(3)]
    assert calls == got                  # hook sees every emitted batch
    pipe.stop()
    server.close()
    assert np.asarray(got).shape == (3, 8)


@pytest.mark.parametrize("executor", ["per-sample", "device"])
def test_pipeline_consume_hook_fires_on_get(executor):
    """The hook contract holds on the prefetching consumer path too:
    get() fires it once per retrieved batch, on either route, and the
    prefetch thread never fires it."""
    from repro.data.pipeline import DSIPipeline
    ds = tiny(n=48)
    server = _server(ds)
    calls = []
    pipe = DSIPipeline(server.open_session(batch_size=8),
                       RemoteStorage(ds), n_workers=2, executor=executor,
                       consume_hook=lambda b: calls.append(
                           b["ids"].tolist()))
    pipe.start_prefetch()
    got = [pipe.get(timeout=60.0)["ids"].tolist() for _ in range(3)]
    assert calls == got
    pipe.stop()
    server.close()


def test_non_dividing_batch_size_exact_accounting():
    """batch_size that does not divide the dataset: the runner targets
    the sampler's real whole-batch epoch pass — no final-batch sample
    overshoot, epoch accounting exact (review finding)."""
    ds = tiny(n=96)
    server = _server(ds, use_ods=False)
    runner = WorkloadRunner(server, RemoteStorage(ds))
    res = runner.run([JobSpec("odd", epochs=2, batch_size=20,
                              gpu_rate=5_000, n_workers=2)], timeout=120)
    server.close()
    job = res.jobs[0]
    epoch_size = (96 // 20) * 20                     # 80
    assert job.samples == 2 * epoch_size             # not 2*96 rounded up
    assert job.batches == 2 * epoch_size // 20
    assert job.epochs_completed == 2
    # batch_size larger than the dataset is rejected loudly
    server2 = _server(ds)
    runner2 = WorkloadRunner(server2, RemoteStorage(ds))
    with pytest.raises(RuntimeError, match="exceeds the dataset"):
        runner2.run([JobSpec("huge", batch_size=200)], timeout=60)
    server2.close()


def test_timeout_expiry_raises_instead_of_truncating():
    """A run() host-timeout must not return truncated results as if
    complete (review finding): it raises under raise_on_error, and the
    inspectable result carries timed_out=True otherwise."""
    ds = tiny(n=256)
    server = _server(ds)
    storage = RemoteStorage(ds)
    trace = [JobSpec("long", epochs=100, batch_size=16, gpu_rate=200,
                     n_workers=2)]
    with pytest.raises(RuntimeError, match="timed out"):
        WorkloadRunner(server, storage,
                       record_ids=False).run(trace, timeout=0.4)
    res = WorkloadRunner(server, storage, record_ids=False).run(
        trace, timeout=0.4, raise_on_error=False)
    assert res.timed_out and res.jobs[0].cancelled and not res.ok
    server.close()
