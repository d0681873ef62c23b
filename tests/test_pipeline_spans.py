"""The pipeline's span recorder (``StageTimes``) and the spans and
counters of the device route.

``StageTimes.span`` adds a body's seconds to its field and nests; the
device route's child spans account for its whole ``next_batch``; and
the stage timers ``fetch`` and ``collate`` time fixed code points."""
import os
import subprocess
import sys
import time

import pytest

from repro.api import AZURE_NC96, SenecaServer
from repro.data.pipeline import DSIPipeline, StageTimes
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny

OLD_KEYS = ("fetch", "decode", "augment", "collate", "batches")
CHILDREN = ("sample", "gather", "fused", "augment", "rows", "admit_rows",
            "collate", "upkeep")
B = 8


class Tick:
    """A clock that moves 1 ms on every reading, so that a timer reads
    how many readings its code points take."""

    def __init__(self):
        self.n = 0

    def now(self) -> float:
        self.n += 1
        return self.n * 1e-3


def test_span_adds_to_its_field_and_nests():
    t = StageTimes(now=Tick().now)
    with t.span("next_batch") as outer:
        with t.span("gather") as inner:
            pass
        with t.span("gather"):
            pass
    assert inner.dt == pytest.approx(1e-3)
    assert t.gather == pytest.approx(2e-3)
    # the outer span holds both inner ones and their four readings
    assert outer.dt == pytest.approx(5e-3)
    assert t.next_batch == pytest.approx(outer.dt)
    assert t.fetch == 0.0 and t.batches == 0


def test_span_on_the_wall_clock():
    t = StageTimes()
    with t.span("collate"):
        time.sleep(0.01)
    assert t.collate >= 0.009


def test_span_counts_a_body_that_raises():
    t = StageTimes(now=Tick().now)
    with pytest.raises(RuntimeError):
        with t.span("upkeep"):
            raise RuntimeError("boom")
    assert t.upkeep == pytest.approx(1e-3)


@pytest.mark.parametrize("name", ["batches", "now", "nothing"])
def test_span_refuses_a_name_that_is_no_time_field(name):
    with pytest.raises((ValueError, AttributeError)):
        StageTimes().span(name)


def test_as_dict_keeps_its_old_keys_and_adds_seconds():
    d = StageTimes().as_dict()
    assert set(OLD_KEYS) <= set(d)
    assert "now" not in d
    assert set(CHILDREN) | {"next_batch", "patchify", "lookup",
                            "admit", "wait"} <= set(d)
    counts = ("batches", "assembles", "row_slices", "taken", "ready")
    assert all(isinstance(d[k], int) for k in counts)
    assert all(isinstance(v, float) for k, v in d.items() if k not in counts)


def test_importing_the_pipeline_does_not_import_jax():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import sys; import repro.data.pipeline; "
            "sys.exit(int('jax' in sys.modules))")
    env = dict(os.environ, PYTHONPATH=src)
    assert subprocess.run([sys.executable, "-c", code],
                          env=env).returncode == 0


def _pipeline(kind: str, clock=None):
    """A device-route pipeline on a tiny dataset, warmed up so that its
    next batch takes the path ``kind`` names: ``cold`` (storage reads
    and the fused kernel), ``hbm`` (augmented rows held in HBM) or
    ``decoded`` (decoded HBM rows, augmented on the device)."""
    ds = tiny(n=32)
    kw = {"split": (1.0, 0.0, 0.0)}
    if kind == "hbm":
        kw = {"split": (0.5, 0.0, 0.5), "hbm_split": (0.0, 0.0, 1.0),
              "device_cache_bytes": int(1.2 * 32 * ds.augmented_bytes())}
    elif kind == "decoded":
        kw = {"split": (0.0, 1.0, 0.0), "hbm_split": (0.0, 1.0, 0.0),
              "device_cache_bytes": int(1.2 * 32 * ds.decoded_bytes())}
    server = SenecaServer.for_dataset(ds, hardware=AZURE_NC96, seed=1,
                                      cache_frac=0.4, use_ods=False, **kw)
    sess = server.open_session(batch_size=B)
    if kind == "decoded":
        for sid in range(32):
            img = ds.decode(ds.encoded(sid), sid)
            assert sess.admit(sid, "decoded", img, img.nbytes)
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                       executor="device", clock=clock, sync_refills=True)
    for _ in range(32 // B if kind == "hbm" else 1):
        pipe.next_batch()
    return pipe, server


def _one_batch(pipe):
    before = pipe.times.as_dict()
    pipe.next_batch()
    after = pipe.times.as_dict()
    return {k: after[k] - before[k] for k in after}


PATHS = {"cold": "fused", "hbm": None, "decoded": "augment"}


@pytest.mark.parametrize("kind", sorted(PATHS))
def test_child_spans_account_for_next_batch(kind):
    pipe, server = _pipeline(kind)
    try:
        d = _one_batch(pipe)
    finally:
        pipe.stop()
        server.close()
    assert d["batches"] == 1
    total = d["next_batch"]
    rest = total - sum(d[k] for k in CHILDREN)
    assert 0.0 <= rest <= max(1e-3, 0.05 * total), (rest, d)
    assert d["gather"] >= d["lookup"] + d["admit"]
    assert d["lookup"] > 0.0
    if PATHS[kind] is not None:
        assert d[PATHS[kind]] > 0.0
    for other in {"fused", "augment"} - {PATHS[kind]}:
        assert d[other] == 0.0
    assert d["decode"] == 0.0
    if kind == "cold":
        assert d["admit"] > 0.0
    else:
        assert d["admit"] == 0.0


@pytest.mark.parametrize("kind", ["cold", "hbm", "decoded"])
def test_fetch_and_collate_time_the_same_code_points(kind):
    """On a clock that moves one tick a reading, the fetch timer reads
    one tick a sample (the lookup of a hit, the storage read of a miss)
    and the collate timer one tick a batch."""
    pipe, server = _pipeline(kind, clock=Tick())
    try:
        d = _one_batch(pipe)
    finally:
        pipe.stop()
        server.close()
    assert d["fetch"] == pytest.approx(B * 1e-3)
    assert d["collate"] == pytest.approx(1e-3)
    assert d["lookup"] == pytest.approx(B * 1e-3)
