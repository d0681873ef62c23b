"""The readers of the image feed's prefetch span and counts, on recorded
inputs."""
import benchpath  # noqa: F401
import pytest

from benchlib import yardstick
from benchlib.catalog import BENCH_DIR, Cell, load_reader
from benchlib.harness import RunRecord


def reader(name):
    return load_reader(BENCH_DIR / "metrics" / f"{name}.py", name)


# before and after the window: 8 batches taken, 6 of them already built
BEFORE = {"batches": 10, "patchify": 0.04, "wait": 0.5, "taken": 4,
          "ready": 2}
DELTA = {"batches": 8, "patchify": 0.01, "wait": 0.012, "taken": 8,
         "ready": 6}
READS = {"feed.wait_ms": 1.5, "feed.ready_share": 75.0}


def record(before=BEFORE, delta=DELTA):
    cell = Cell("c", 1, {}, {"dataset": {"crop_hw": [224, 224]}}, [], [], 10)
    return RunRecord(
        cell, 128, 1e9, yardstick.peaks("TPU v5 lite"), 12.5, window_s=2.0,
        step_intervals=[0.4, 0.5, 0.5, 0.6], times_before=dict(before),
        times_after={k: before[k] + delta.get(k, 0) for k in before})


@pytest.mark.parametrize("name", sorted(READS))
def test_feed_readers(name):
    assert reader(name)(record()) == pytest.approx(READS[name])


def test_ready_share_reads_100_when_every_batch_was_built():
    assert reader("feed.ready_share")(
        record(delta=dict(DELTA, ready=8))) == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(READS))
def test_feed_readers_read_nothing_without_batches_taken(name):
    assert reader(name)(record(delta=dict(DELTA, taken=0))) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_feed_readers_read_nothing_from_a_program_without_the_fields(name):
    """A program whose feed takes its batches on the caller's thread:
    ``StageTimes`` has no ``wait``, ``taken`` or ``ready``."""
    old = {k: v for k, v in BEFORE.items()
           if k not in ("wait", "taken", "ready")}
    assert reader(name)(record(old)) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_feed_readers_read_nothing_with_one_field_missing(name):
    key = "wait" if name == "feed.wait_ms" else "ready"
    old = {k: v for k, v in BEFORE.items() if k != key}
    assert reader(name)(record(old)) is None
