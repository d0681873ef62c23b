"""The image feed builds its batches on the pipeline's prefetch thread.

``image_batch_source`` starts ``DSIPipeline.start_prefetch`` on its first
call and takes every batch with ``get(timeout=None)``: the batches are
bitwise those of serial ``next_batch()`` calls on a second pipeline of
the same seed, the thread builds the next batch while the caller holds
one, a route error reaches the caller, and ``stop()`` leaves no thread.
"""
import threading
import time
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.data.synthetic import caption_ids, tiny
from repro.launch.train import image_batch_source, patchify_stub

T, D, CLASSES = 4, 16, 10
BATCH, SAMPLES = 8, 32
ROUTES = {
    "device-cold": dict(executor="device"),
    "device-hbm": dict(executor="device", device_cache_bytes=4 << 20),
    "per-sample": dict(executor="per-sample"),
}


def _encoder():
    return SimpleNamespace(cfg=SimpleNamespace(
        frontend_tokens=T, d_model=D, n_classes=CLASSES))


def _source(model=None, hook=None, **route):
    out = image_batch_source(model or _encoder(), BATCH, seed=3,
                             dataset=tiny(n=SAMPLES), consume_hook=hook,
                             **route)
    # background refills race the sampler (ODS serves what they cached),
    # so serial runs differ among themselves unless refills run inline
    out[1]._sync_refills = True
    return out


def _serial(n, model=None, **route):
    """``n`` raw batches of serial ``next_batch()`` calls."""
    _, pipe, server = _source(model, **route)
    try:
        return [pipe.next_batch() for _ in range(n)]
    finally:
        pipe.stop()
        server.close()


def _counting(pipe):
    """The route's calls, counted as they return."""
    built, route = [], pipe._next_batch_device

    def counted():
        out = route()
        built.append(threading.current_thread())
        return out

    pipe._next_batch_device = counted
    return built


def _wait_for(cond, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


def _equal(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_feed_batches_equal_the_serial_ones_bitwise(route):
    n = 2 * SAMPLES // BATCH + 2      # past two epochs: warm HBM hits
    raws = []
    source, pipe, server = _source(hook=raws.append, **ROUTES[route])
    try:
        fed = [source() for _ in range(n)]
    finally:
        pipe.stop()
        server.close()
    serial = _serial(n, **ROUTES[route])
    for got, raw, want in zip(fed, raws, serial):
        _equal(raw["ids"], want["ids"])
        _equal(raw["images"], want["images"])
        _equal(raw["labels"], want["labels"])
        _equal(got["patch_embeds"], patchify_stub(want["images"], T, D))
        _equal(got["labels"], jnp.asarray(want["labels"] % CLASSES,
                                          jnp.int32))
    if route == "device-hbm":
        assert server.stats()["hbm"]["augmented"]["hbm_hits"] > 0


def test_vlm_feed_tokens_and_labels_equal_the_serial_ones():
    from repro.models.model import build
    cfg = registry.get_reduced("kimi-vl-a3b")
    model = build(cfg)
    n = SAMPLES // BATCH + 1
    source, pipe, server = _source(model, executor="device")
    try:
        fed = [source() for _ in range(n)]
    finally:
        pipe.stop()
        server.close()
    P, k = cfg.frontend_tokens, cfg.text_tokens
    for got, want in zip(fed, _serial(n, model, executor="device")):
        text = caption_ids(pipe.ds.seed, want["ids"], k + 1,
                           cfg.vocab_size)
        _equal(got["patch_embeds"],
               patchify_stub(want["images"], P, cfg.d_model))
        _equal(got["tokens"], text[:, :-1])
        _equal(got["labels"], np.concatenate(
            [np.full((BATCH, P), -1, np.int32), text[:, 1:]], 1))


def test_next_batch_calls_before_the_feed_continue_the_same_ids():
    raws = []
    source, pipe, server = _source(hook=raws.append, executor="device")
    try:
        first = [pipe.next_batch()["ids"] for _ in range(2)]
        for _ in range(4):
            source()
    finally:
        pipe.stop()
        server.close()
    ids = first + [r["ids"] for r in raws[2:6]]
    want = [b["ids"] for b in _serial(6, executor="device")]
    for a, b in zip(ids, want):
        _equal(a, b)
    seen = np.concatenate(ids[:SAMPLES // BATCH])
    assert sorted(seen.tolist()) == list(range(SAMPLES))


def test_the_thread_builds_ahead_while_the_caller_holds_a_batch():
    source, pipe, server = _source(executor="device")
    built = _counting(pipe)
    try:
        source()
        t = pipe.times
        assert t.taken == 1
        # no further call: the thread builds the next batches alone, up
        # to two queued and one held
        _wait_for(lambda: len(built) == 4)
        time.sleep(0.3)
        assert len(built) == t.batches == 4
        ready = t.ready
        source()
        assert (t.taken, t.ready) == (2, ready + 1)
        _wait_for(lambda: len(built) == 5)
        assert t.wait > 0.0
        assert all(th is pipe._thread for th in built)
        # a caller other than the thread may not run the route now
        with pytest.raises(RuntimeError, match=r"get\(\)"):
            pipe.next_batch()
    finally:
        pipe.stop()
        server.close()


def test_the_hook_sees_only_the_batches_handed_out_on_the_callers_thread():
    """As with serial calls, a consume hook sees each batch the caller
    takes, on the caller's thread, and none the thread built ahead."""
    seen = []
    source, pipe, server = _source(
        hook=lambda raw: seen.append((threading.current_thread(),
                                      raw["ids"])), executor="device")
    built = _counting(pipe)
    try:
        for _ in range(3):
            source()
        _wait_for(lambda: len(built) == 6)
        time.sleep(0.3)
        assert len(seen) == 3 and len(built) == 6
        assert all(th is threading.current_thread() for th, _ in seen)
    finally:
        pipe.stop()
        server.close()
    want = _serial(3, executor="device")
    for (_, ids), b in zip(seen, want):
        _equal(ids, b["ids"])


def test_a_route_error_reaches_the_caller():
    source, pipe, server = _source(executor="device")
    route = pipe._next_batch_device
    calls = []

    def fail_second():
        calls.append(1)
        if len(calls) == 2:
            raise ValueError("synthetic route failure")
        return route()

    pipe._next_batch_device = fail_second
    try:
        assert source()["patch_embeds"].shape == (BATCH, T, D)
        with pytest.raises(RuntimeError, match="prefetch thread died") as e:
            source()
        assert isinstance(e.value.__cause__, ValueError)
        assert server.stats()["telemetry"]["errors"]["prefetch"] == 1
    finally:
        pipe.stop()
        server.close()


def test_get_without_a_deadline_outlasts_a_slow_producer():
    source, pipe, server = _source(executor="device")
    route = pipe._next_batch_device

    def slow():
        time.sleep(0.7)                 # several of get()'s 0.2 s polls
        return route()

    pipe._next_batch_device = slow
    try:
        for _ in range(2):
            assert source()["labels"].shape == (BATCH,)
    finally:
        pipe.stop()
        server.close()


def test_get_without_a_deadline_refuses_a_pipeline_with_no_thread():
    _, pipe, server = _source(executor="device")
    try:
        with pytest.raises(RuntimeError, match="start_prefetch"):
            pipe.get(timeout=None)
    finally:
        pipe.stop()
        server.close()


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_stop_leaves_no_prefetch_thread(route):
    source, pipe, server = _source(**ROUTES[route])
    source()
    thread = pipe._thread
    pipe.stop()
    server.close()
    assert thread is not None and not thread.is_alive()
