"""The check passes a sound run and fails a broken one.

Runs the whole harness at a tiny size on the CPU, past its look for a
chip, with the timed path broken underneath in each way a one-chip
training cell can break; and reads the float8 control, which has to
fail too."""
import benchpath
import jax
import jax.numpy as jnp
import pytest

from benchlib import check, harness
from benchlib.catalog import Catalog
from pathlib import Path

DATA = Path(benchpath.DATA)
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def cell(name):
    return Catalog(DATA / "spec.json", dirs=[DATA]).cell(name)


def run(name, seed, **kw):
    return harness.run(cell(name), seed, 0.5, False, 0.0, DEVICE, **kw)


@pytest.mark.parametrize("name, seed", [("tiny-cold", 12345),
                                        ("tiny-warm", 2147483725)])
def test_sound_run_is_correct(name, seed):
    out = run(name, seed)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "step_ms_p90",
                                   "setup_s"}


def _unchanged(model, parallel, opt):
    def step(params, opt_state, batch):
        return params, opt_state, {"loss": model.loss(params, batch),
                                   "grad_norm": jnp.zeros((), jnp.float32)}
    return step


def _half_batch(model, parallel, opt):
    from repro.train.step import build_train_step
    real = build_train_step(model, parallel, opt)

    def step(params, opt_state, batch):
        half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
        return real(params, opt_state, half)
    return step


@pytest.mark.parametrize("builder", [_unchanged, _half_batch],
                         ids=["state-unchanged", "half-batch"])
def test_broken_step_is_not_correct(builder):
    out = run("tiny-cold", 12345, step_builder=builder)
    assert not out["correct"], out["checks"]


def test_altered_row_is_not_correct(monkeypatch):
    from repro.kernels.augment import ops
    real = ops.decode_augment_batch_seeded

    def altered(*a, **kw):
        return real(*a, **kw).at[0, 0, 0, 0].add(0.01)
    monkeypatch.setattr(ops, "decode_augment_batch_seeded", altered)
    out = run("tiny-cold", 12345)
    assert not out["correct"]
    assert out["checks"]["row_gap"]["value"] > 0.009


def test_control_and_half_batch_fail_the_limits():
    import control
    c = cell("tiny-cold")
    r = control.readings(c, 1)
    limits = {k: v for k, v in c.config["limits"].items()
              if k in r["program"]}
    assert check.verdict(r["program"], limits)[0]
    assert not check.verdict(r["control_fp8"], limits)[0]
    assert not check.verdict(r["fault_half_batch"], limits)[0]
    assert r["row_gap_bf16"] > c.traffic["limits"]["row_gap"] > r["row_gap"]
