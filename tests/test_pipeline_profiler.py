"""The device route's spans in a profiler trace.

The image feed builds its batches on the pipeline's prefetch thread.  A
short ``jax.profiler`` trace of two batches of the feed holds, on that
thread, one ``seneca.next_batch`` a built batch with each child phase
of the device route inside it once, and, on the caller's thread, one
``seneca.wait`` followed by one ``seneca.patchify`` inside each of the
caller's own annotations around the feed.  Kept in a file of its own:
the profiler is global to the process."""
import glob
import os
import time
from types import SimpleNamespace

import jax
import pytest

from repro.data.synthetic import tiny
from repro.launch.train import image_batch_source

CHILDREN = ("sample", "gather", "fused", "rows", "admit_rows", "collate",
            "upkeep")
BATCHES = 2
AHEAD = 3          # batches the prefetch thread holds: two queued, one held


def _host_events(path):
    """(name, start, end, thread) of every host event; ``thread`` names
    the trace line, one a thread."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     (plane.name, i)) for e in line.events]
    return out


def _counting(pipe):
    """Count the route's calls as they return, after their spans close."""
    built, route = [], pipe._next_batch_device

    def counted():
        out = route()
        built.append(1)
        return out

    pipe._next_batch_device = counted
    return built


def _built(built, n, timeout=120.0):
    """Wait until the prefetch thread has built ``n`` batches."""
    deadline = time.monotonic() + timeout
    while len(built) < n:
        assert time.monotonic() < deadline, (len(built), n)
        time.sleep(0.01)


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """(caller annotations, seneca.* annotations) of a traced run."""
    log_dir = tmp_path_factory.mktemp("trace")
    model = SimpleNamespace(cfg=SimpleNamespace(frontend_tokens=4,
                                                d_model=16, n_classes=10))
    next_batch, pipe, server = image_batch_source(
        model, 8, dataset=tiny(n=32), executor="device")
    built = _counting(pipe)
    try:
        next_batch()                     # compiles outside the trace
        # the thread is blocked on a full queue: nothing is half built
        _built(built, 1 + AHEAD)
        jax.profiler.start_trace(str(log_dir))
        try:
            for _ in range(BATCHES):
                with jax.profiler.TraceAnnotation("caller.next_batch"):
                    jax.block_until_ready(next_batch())
            # each batch taken frees a slot, and the thread builds one
            _built(built, 1 + AHEAD + BATCHES)
        finally:
            jax.profiler.stop_trace()
    finally:
        pipe.stop()
        server.close()
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the profiler wrote no trace"
    evs = _host_events(found[0])
    return ([e for e in evs if e[0] == "caller.next_batch"],
            [e for e in evs if e[0].startswith("seneca.")])


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def _named(seneca, name):
    return [e for e in seneca if e[0] == "seneca." + name]


def _caller_thread(callers):
    threads = {c[3] for c in callers}
    assert len(threads) == 1, threads
    return threads.pop()


def test_one_next_batch_span_in_each_callers_annotation(events):
    """The caller's annotation holds the feed's take, ``seneca.wait``,
    once; the route's ``seneca.next_batch`` runs once a built batch on
    the prefetch thread, not the caller's."""
    callers, seneca = events
    assert len(callers) == BATCHES
    caller = _caller_thread(callers)
    waits = _named(seneca, "wait")
    for c in callers:
        assert sum(_inside(w, c) for w in waits) == 1
    tops = _named(seneca, "next_batch")
    assert len(tops) == BATCHES
    assert len({t[3] for t in tops}) == 1
    assert all(t[3] != caller for t in tops), tops


@pytest.mark.parametrize("name", CHILDREN)
def test_each_phase_is_one_span_a_batch_inside_next_batch(events, name):
    callers, seneca = events
    tops = _named(seneca, "next_batch")
    spans = _named(seneca, name)
    assert len(spans) == BATCHES, spans
    for ev in spans:
        assert any(_inside(ev, t) and ev[3] == t[3] for t in tops), ev
    for t in tops:
        assert sum(_inside(ev, t) and ev[3] == t[3] for ev in spans) == 1


def test_patchify_follows_next_batch_inside_the_caller(events):
    """Inside each caller annotation: one ``seneca.wait`` for the built
    batch, then one ``seneca.patchify``, not overlapping it."""
    callers, seneca = events
    waits = _named(seneca, "wait")
    spans = _named(seneca, "patchify")
    assert len(spans) == len(waits) == BATCHES
    for c in callers:
        (w,) = [e for e in waits if _inside(e, c)]
        (p,) = [e for e in spans if _inside(e, c)]
        assert w[3] == p[3] == c[3]
        assert w[2] <= p[1], (w, p)


def test_no_span_outside_the_callers_annotations(events):
    """On the caller's thread every ``seneca.*`` span is the feed's own
    and lies inside a caller annotation: no route span is timed there."""
    callers, seneca = events
    caller = _caller_thread(callers)
    feed = {"seneca.wait", "seneca.patchify"}
    route = {"seneca." + n for n in CHILDREN + ("next_batch",)}
    for ev in seneca:
        assert ev[0] in feed | route, ev
        if ev[3] == caller:
            assert ev[0] in feed, ev
            assert any(_inside(ev, c) for c in callers), ev
        else:
            assert ev[0] in route, ev
