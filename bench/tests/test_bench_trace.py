"""The trace reduction, on hand-made planes and on a trace recorded on a
TPU v5e (three steps of a two-layer ViT-B-wide model fed by the fused
decode+augment kernel, each wrapped in the harness's annotations)."""
import os

import benchpath
import pytest

from benchlib import xtrace

RECORDED = os.path.join(benchpath.DATA, "v5e_three_steps.xplane.pb")


def planes():
    ms = 1_000_000
    host = ("/host:CPU", [("python", [
        ("bench.next_batch", 0 * ms, 10 * ms),
        ("bench.step", 10 * ms, 30 * ms),
        ("bench.next_batch", 50 * ms, 10 * ms),
        ("bench.step", 60 * ms, 40 * ms),
        ("other", 0, 100 * ms)])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("fusion.1", 5 * ms, 3 * ms),
                     ("convolution.2", 12 * ms, 20 * ms),
                     ("fusion.1", 30 * ms, 10 * ms),      # overlaps the last
                     ("convolution.2", 62 * ms, 30 * ms)]),
        ("XLA Modules", [("jit_decode_augment(1)", 5 * ms, 3 * ms),
                         ("jit_step(2)", 12 * ms, 28 * ms),
                         ("jit_step(2)", 62 * ms, 30 * ms)])])
    return [host, dev]


def test_reduce_hand_made_planes():
    s = xtrace.reduce(planes())
    assert s.window_s == pytest.approx(0.1)
    # busy: 5-8, 12-40, 62-92 ms
    assert s.busy_s == pytest.approx(0.061)
    assert s.module_time("jit_step") == (2, pytest.approx(0.058))
    assert s.module_time("decode_augment") == (1, pytest.approx(0.003))
    assert s.ops["fusion.1"] == pytest.approx(0.013)
    # idle 0-5, 8-10 and 50-60 in next_batch, 10-12, 60-62 and 92-100
    # in step, 40-50 with no annotation open
    assert s.idle_by_host["next_batch"] == pytest.approx(0.017)
    assert s.idle_by_host["step"] == pytest.approx(0.012)
    assert s.idle_by_host["harness"] == pytest.approx(0.010)
    assert s.spans["bench.step"] == (2, pytest.approx(0.07))
    b = xtrace.breakdown(s)
    assert b["device_ops"][0] == ["convolution.2", pytest.approx(0.05)]
    assert len(b["idle_gaps"]) == 3


def test_a_trace_without_annotations_or_device_is_refused():
    with pytest.raises(ValueError):
        xtrace.reduce([planes()[1]])
    with pytest.raises(ValueError):
        xtrace.reduce([planes()[0]])


def test_reduce_a_trace_recorded_on_the_chip():
    s = xtrace.reduce(xtrace.read_planes(RECORDED))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.195087956)
    assert s.busy_s == pytest.approx(0.102649856)
    assert s.module_time("jit_step") == (3, pytest.approx(0.099866982))
    assert s.module_time("decode_augment") == (3, pytest.approx(0.00142852))
    assert s.spans["bench.step"][0] == 3
    # the probe slept 10 ms after each step, outside any annotation
    assert s.idle_by_host["harness"] == pytest.approx(0.0205, abs=1e-3)
    ops = xtrace.breakdown(s)["device_ops"]
    assert len(ops) == 10 and not any(n.startswith("while") for n, _ in ops)
    assert all(" = " not in n for n, _ in ops)
