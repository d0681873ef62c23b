"""The device route's spans in a profiler trace.

A short ``jax.profiler`` trace of two batches of the image feed holds
each ``seneca.*`` annotation once a batch, inside the caller's own
annotation around ``next_batch``, and each child phase of the device
route inside ``seneca.next_batch``.  Kept in a file of its own: the
profiler is global to the process."""
import glob
import os
from types import SimpleNamespace

import jax
import pytest

from repro.data.synthetic import tiny
from repro.launch.train import image_batch_source

CHILDREN = ("sample", "gather", "fused", "rows", "admit_rows", "collate",
            "upkeep")
BATCHES = 2


def _host_events(path):
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return out


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """(caller annotations, seneca.* annotations) of a traced run."""
    log_dir = tmp_path_factory.mktemp("trace")
    model = SimpleNamespace(cfg=SimpleNamespace(frontend_tokens=4,
                                                d_model=16, n_classes=10))
    next_batch, pipe, server = image_batch_source(
        model, 8, dataset=tiny(n=32), executor="device")
    try:
        next_batch()                     # compiles outside the trace
        jax.profiler.start_trace(str(log_dir))
        try:
            for _ in range(BATCHES):
                with jax.profiler.TraceAnnotation("caller.next_batch"):
                    jax.block_until_ready(next_batch())
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


def test_one_next_batch_span_in_each_callers_annotation(events):
    callers, seneca = events
    assert len(callers) == BATCHES
    tops = _named(seneca, "next_batch")
    assert len(tops) == BATCHES
    for c in callers:
        assert sum(_inside(t, c) for t in tops) == 1


@pytest.mark.parametrize("name", CHILDREN)
def test_each_phase_is_one_span_a_batch_inside_next_batch(events, name):
    callers, seneca = events
    tops = _named(seneca, "next_batch")
    spans = _named(seneca, name)
    assert len(spans) == BATCHES, spans
    for ev in spans:
        assert any(_inside(ev, c) for c in callers), ev
        assert any(_inside(ev, t) for t in tops), ev


def test_patchify_follows_next_batch_inside_the_caller(events):
    callers, seneca = events
    tops = _named(seneca, "next_batch")
    spans = _named(seneca, "patchify")
    assert len(spans) == BATCHES
    for ev in spans:
        assert any(_inside(ev, c) for c in callers), ev
        # after the device route's span, not overlapping it
        assert not any(t[1] < ev[2] and ev[1] < t[2] for t in tops), ev
        assert any(t[2] <= ev[1] for t in tops), ev


def test_no_span_outside_the_callers_annotations(events):
    callers, seneca = events
    known = {"seneca." + n for n in CHILDREN + ("next_batch", "patchify")}
    for ev in seneca:
        assert ev[0] in known, ev
        assert any(_inside(ev, c) for c in callers), ev
