"""The readers of the device route's spans and counters, on recorded inputs."""
import benchpath  # noqa: F401
import pytest

from benchlib import yardstick
from benchlib.catalog import BENCH_DIR, Cell, load_reader
from benchlib.harness import RunRecord


def reader(name):
    return load_reader(BENCH_DIR / "metrics" / f"{name}.py", name)


# seconds before and after the window, over 4 batches
SPAN_BEFORE = {"batches": 3, "fetch": 1.0, "collate": 0.1, "decode": 0.5,
               "augment": 0.2, "next_batch": 2.0, "sample": 0.1,
               "gather": 1.0, "fused": 0.3, "rows": 0.1, "admit_rows": 0.05,
               "upkeep": 0.02, "patchify": 0.04, "lookup": 0.2,
               "admit": 0.3}
SPAN_DELTA = {"batches": 4, "fetch": 0.2, "collate": 0.04, "decode": 0.0,
              "augment": 0.0, "next_batch": 1.0, "sample": 0.04,
              "gather": 0.6, "fused": 0.12, "rows": 0.08,
              "admit_rows": 0.004, "upkeep": 0.02, "patchify": 0.016,
              "lookup": 0.1, "admit": 0.24}
SPAN_READS = {
    "pipeline.next_batch_ms": 250.0,
    "pipeline.sample_ms": 10.0,
    "pipeline.lookup_ms": 25.0,
    "pipeline.admit_ms": 61.0,
    "pipeline.fused_ms": 30.0,
    "pipeline.rows_ms": 20.0,
    "pipeline.upkeep_ms": 5.0,
    # 1.0 - (0.04 + 0.6 + 0.12 + 0.0 + 0.08 + 0.004 + 0.04 + 0.02)
    "pipeline.self_ms": 24.0,
    "feed.patchify_ms": 4.0,
}


def span_record(before=SPAN_BEFORE, delta=SPAN_DELTA):
    cell = Cell("c", 1, {}, {"dataset": {"crop_hw": [224, 224]}}, [], [], 10)
    return RunRecord(
        cell, 128, 1e9, yardstick.peaks("TPU v5 lite"), 12.5, window_s=2.0,
        step_intervals=[0.4, 0.5, 0.5, 0.6], times_before=dict(before),
        times_after={k: before[k] + delta.get(k, 0) for k in before})


@pytest.mark.parametrize("name", sorted(SPAN_READS))
def test_span_and_counter_readers(name):
    assert reader(name)(span_record()) == pytest.approx(SPAN_READS[name])


@pytest.mark.parametrize("name", sorted(SPAN_READS))
def test_span_readers_read_nothing_without_batches(name):
    assert reader(name)(span_record(delta=dict(SPAN_DELTA, batches=0))) \
        is None


@pytest.mark.parametrize("name", sorted(SPAN_READS))
def test_span_readers_read_nothing_from_a_program_without_spans(name):
    """A program whose ``StageTimes`` has only the stage timers."""
    old = {k: SPAN_BEFORE[k] for k in ("batches", "fetch", "decode",
                                       "augment", "collate")}
    assert reader(name)(span_record(old)) is None
