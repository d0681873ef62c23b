"""Every metric reader on recorded inputs."""
import benchpath  # noqa: F401
import pytest

from benchlib import xtrace, yardstick
from benchlib.catalog import BENCH_DIR, Cell, load_reader
from benchlib.harness import RunRecord


def reader(name):
    return load_reader(BENCH_DIR / "metrics" / f"{name}.py", name)


def record(trace=None, hbm=True):
    cell = Cell("c", 1, {}, {"dataset": {"crop_hw": [224, 224]}}, [], [], 10)
    stats_b = {"telemetry": {"serve_counts": {"storage": 10, "augmented": 5}}}
    stats_a = {"telemetry": {"serve_counts": {"storage": 10,
                                              "augmented": 405}}}
    if hbm:
        stats_b["hbm"] = {"augmented": {"hbm_hits": 5}}
        stats_a["hbm"] = {"augmented": {"hbm_hits": 305}}
    rec = RunRecord(cell, 128, 1e9, yardstick.peaks("TPU v5 lite"), 12.5,
                    window_s=2.0, step_intervals=[0.4, 0.5, 0.5, 0.6],
                    spans=[("next_batch", 0.0, 0.1), ("step", 0.1, 0.4),
                           ("next_batch", 0.4, 0.6), ("step", 0.6, 2.0)],
                    times_before={"batches": 3, "fetch": 1.0,
                                  "collate": 0.1},
                    times_after={"batches": 7, "fetch": 1.2, "collate": 0.14},
                    stats_before=stats_b, stats_after=stats_a, trace=trace)
    return rec


def summary():
    return xtrace.TraceSummary(
        window_s=2.0, busy_s=1.5, n_devices=1,
        modules={"jit_step(123)": (4, 1.2),
                 "jit_decode_augment(7)": (4, 0.0004)})


def test_end_to_end_readers():
    rec = record()
    assert reader("samples_per_s")(rec) == pytest.approx(4 * 128 / 2.0)
    assert reader("step_ms_p90")(rec) == pytest.approx(570.0)
    assert reader("setup_s")(rec) == 12.5


def test_host_and_program_readers():
    rec = record()
    assert reader("data_wait_share")(rec) == pytest.approx(15.0)
    assert reader("pipeline.fetch_ms")(rec) == pytest.approx(50.0)
    assert reader("pipeline.collate_ms")(rec) == pytest.approx(10.0)
    assert reader("cache.hbm_hit_share")(rec) == pytest.approx(75.0)
    assert reader("cache.hbm_hit_share")(record(hbm=False)) is None
    assert reader("mfu")(rec) == pytest.approx(
        100 * 1e9 * 512 / (2.0 * 197e12))


def test_trace_readers():
    assert reader("train_step.device_ms")(record()) is None
    assert reader("decode_augment_roofline")(record()) is None
    assert reader("device.idle_share")(record()) is None
    rec = record(summary())
    assert reader("train_step.device_ms")(rec) == pytest.approx(300.0)
    assert reader("device.idle_share")(rec) == pytest.approx(25.0)
    need = 4 * yardstick.decode_augment_bytes(128, (224, 224))
    assert reader("decode_augment_roofline")(rec) == pytest.approx(
        100 * need / 819e9 / 0.0004)


def test_a_kernel_that_did_not_run_reads_nothing():
    s = summary()
    s.modules = {"jit_step(1)": (4, 1.2)}
    assert reader("decode_augment_roofline")(record(s)) is None
